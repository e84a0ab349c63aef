"""Mixture-of-Experts transformer LM.

Covers: deepseek-moe-16b (2 shared + 64 routed experts, top-6, fine-grained)
and arctic-480b (128 routed top-2 + dense residual FFN in parallel).

Dispatch is sort-based with a fixed per-expert capacity C — tokens are
sorted by assigned expert, packed into an (E, C, d) buffer, run through a
batched expert FFN einsum, and scattered back weighted by router gates.
With experts sharded over the `model` mesh axis (expert parallelism) XLA
inserts the all-to-alls at the buffer resharding points.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.base import (Unit, dense_unit, init_stacked, scan_layers,
                               scan_layers_with_cache, stacked_units)

from repro.dist.ctx import constrain_expert, constrain_layer_io, constrain_tokens

PyTree = Any


# ------------------------------------------------------------------ MoE core

def moe_ffn_init(key, cfg: ArchConfig):
    ks = jax.random.split(key, 5)
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    p = {
        "router": L.dense_init(ks[0], d, E),
        "w_gate": jax.random.normal(ks[1], (E, d, ff), jnp.float32) / math.sqrt(d),
        "w_up": jax.random.normal(ks[2], (E, d, ff), jnp.float32) / math.sqrt(d),
        "w_down": jax.random.normal(ks[3], (E, ff, d), jnp.float32) / math.sqrt(ff),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = L.swiglu_init(ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def moe_ffn(p, x, cfg: ArchConfig):
    """x: (B, S, D) -> (B, S, D).  Top-k routing with capacity drop."""
    b, s, d = x.shape
    n = b * s
    E, K = cfg.n_experts, cfg.top_k
    xt = constrain_tokens(x.reshape(n, d))

    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)                   # (N, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- sort-based dispatch ----
    C = int(math.ceil(n * K / E * cfg.capacity_factor))
    flat_expert = expert_ids.reshape(-1)                              # (N*K,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(n), K)

    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]

    # position of each routed token within its expert's capacity buffer
    ones = jnp.ones_like(sorted_expert)
    seg_pos = jax.lax.associative_scan(jnp.add, ones) - 1
    # subtract start offset of each expert's segment
    counts = jnp.bincount(sorted_expert, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    within = seg_pos - starts[sorted_expert]
    keep = within < C

    buf_idx = sorted_expert * C + jnp.where(keep, within, 0)
    buffer = jnp.zeros((E * C, d), x.dtype)
    gathered = xt[sorted_token] * keep[:, None].astype(x.dtype)
    buffer = buffer.at[buf_idx].add(gathered)                        # (E*C, d)
    buffer = constrain_expert(buffer.reshape(E, C, d))

    # ---- expert FFN (batched einsum; E dim shards over `model` axis) ----
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buffer, p["w_gate"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", buffer, p["w_up"].astype(x.dtype))
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(x.dtype))
    out_buf = constrain_expert(out_buf).reshape(E * C, d)

    # ---- scatter back ----
    contrib = out_buf[buf_idx] * (sorted_gate * keep)[:, None].astype(x.dtype)
    out = constrain_tokens(jnp.zeros((n, d), x.dtype).at[sorted_token].add(contrib))

    if cfg.n_shared_experts > 0:
        out = out + L.swiglu(p["shared"], xt)
    return out.reshape(b, s, d)


def _local_dispatch_ffn(xt, logits, wg, wu, wd, cfg: ArchConfig,
                        e_base, e_local: int):
    """Dispatch xt (n, d) to THIS shard's experts [e_base, e_base+e_local).

    Sort-based packing exactly as moe_ffn, but over the local expert range —
    runs inside shard_map, so n and the buffer stay per-device sized."""
    n, d = xt.shape
    K = cfg.top_k
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    C = int(math.ceil(n * K / cfg.n_experts * cfg.capacity_factor))
    flat_expert = expert_ids.reshape(-1) - e_base          # local ids
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(n), K)
    mine = (flat_expert >= 0) & (flat_expert < e_local)
    flat_expert = jnp.where(mine, flat_expert, e_local)    # park foreign ids

    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = jnp.where(mine[order], flat_gate[order], 0.0)

    ones = jnp.ones_like(sorted_expert)
    seg_pos = jnp.cumsum(ones) - 1
    counts = jnp.bincount(sorted_expert, length=e_local + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    within = seg_pos - starts[sorted_expert]
    keep = (within < C) & (sorted_expert < e_local)

    buf_idx = jnp.where(keep, sorted_expert * C + within, e_local * C)
    buffer = jnp.zeros((e_local * C + 1, d), xt.dtype)
    gathered = xt[sorted_token] * keep[:, None].astype(xt.dtype)
    buffer = buffer.at[buf_idx].add(gathered)[:-1].reshape(e_local, C, d)

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buffer, wg.astype(xt.dtype)))
    u = jnp.einsum("ecd,edf->ecf", buffer, wu.astype(xt.dtype))
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, wd.astype(xt.dtype))
    out_buf = jnp.concatenate(
        [out_buf.reshape(e_local * C, d), jnp.zeros((1, d), xt.dtype)])

    contrib = out_buf[buf_idx] * (sorted_gate * keep)[:, None].astype(xt.dtype)
    return jnp.zeros((n, d), xt.dtype).at[sorted_token].add(contrib)


def moe_ffn_spmd(p, x, cfg: ArchConfig):
    """Expert-parallel MoE under shard_map.

    Tokens arrive data-sharded (replicated over `model`); each model-shard
    owns E/tp experts, packs only its own assignments locally, and a psum
    over `model` combines partial outputs — one residual-sized all-reduce
    per layer.  This replaces the global sort-based dispatch, which GSPMD
    degenerates into replicated (N*K, d) gathers (hundreds of GB/device at
    1M tokens)."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import ctx as dctx

    mesh = dctx._STATE["mesh"]
    daxes = dctx._STATE["batch_axes"]
    maxis = dctx._STATE["model_axis"]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get(maxis, 1)
    if cfg.n_experts % tp != 0:
        return moe_ffn(p, x, cfg)
    e_local = cfg.n_experts // tp
    b, s, d = x.shape

    def body(xb, router, wg, wu, wd):
        nb = xb.shape[0] * xb.shape[1]
        xt = xb.reshape(nb, d)
        logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
        e_base = jax.lax.axis_index(maxis) * e_local
        out = _local_dispatch_ffn(xt, logits, wg, wu, wd, cfg, e_base, e_local)
        out = jax.lax.psum(out, maxis)
        return out.reshape(xb.shape)

    bspec = P(daxes, None, None)
    espec = P(maxis, None, None)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(bspec, P(None, None), espec, espec, espec),
                       out_specs=bspec, check_vma=False)
    out = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if cfg.n_shared_experts > 0:
        xt = x.reshape(b * s, d)
        out = out + L.swiglu(p["shared"], xt).reshape(b, s, d)
    return out


def moe_ffn_auto(p, x, cfg: ArchConfig):
    """Route to the shard_map expert-parallel path when a sharding context
    is active, else the single-logical-device dispatch."""
    from repro.dist import ctx as dctx
    if dctx.active():
        return moe_ffn_spmd(p, x, cfg)
    return moe_ffn(p, x, cfg)


def moe_ffn_exact(p, x, cfg: ArchConfig):
    """Dropless MoE via per-token expert-weight gather — exact (no capacity),
    used for decode where N is small and capacity-dropping would make decode
    diverge from the batched forward."""
    b, s, d = x.shape
    n = b * s
    K = cfg.top_k
    xt = x.reshape(n, d)
    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    wg = p["w_gate"][expert_ids].astype(x.dtype)   # (N, K, d, ff)
    wu = p["w_up"][expert_ids].astype(x.dtype)
    wd = p["w_down"][expert_ids].astype(x.dtype)
    g = jax.nn.silu(jnp.einsum("nd,nkdf->nkf", xt, wg))
    u = jnp.einsum("nd,nkdf->nkf", xt, wu)
    y = jnp.einsum("nkf,nkfd->nkd", g * u, wd)
    out = jnp.einsum("nkd,nk->nd", y, gate_vals.astype(x.dtype))
    if cfg.n_shared_experts > 0:
        out = out + L.swiglu(p["shared"], xt)
    return out.reshape(b, s, d)


# --------------------------------------------------------------------- model

def init_layer(cfg: ArchConfig):
    def one(key):
        k1, k2, k3 = jax.random.split(key, 3)
        p = {
            "ln1": L.rmsnorm_init(cfg.d_model),
            "attn": L.gqa_attention_init(k1, cfg.d_model, cfg.n_heads,
                                         cfg.kv_heads, cfg.head_dim, cfg.qkv_bias),
            "ln2": L.rmsnorm_init(cfg.d_model),
            "moe": moe_ffn_init(k2, cfg),
        }
        if cfg.dense_residual:
            p["dense_mlp"] = L.swiglu_init(k3, cfg.d_model, cfg.d_ff)
        return p
    return one


def init(cfg: ArchConfig, key) -> PyTree:
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    return {
        "embed": {"tok": L.embed_init(k_embed, cfg.vocab_padded, cfg.d_model)},
        "layers": init_stacked(init_layer(cfg), k_layers, cfg.n_layers),
        "head": {
            "final_norm": L.rmsnorm_init(cfg.d_model),
            "w": L.dense_init(k_head, cfg.d_model, cfg.vocab_padded),
        },
    }


def unit_spec(cfg: ArchConfig) -> list[Unit]:
    return [dense_unit("embed")] + stacked_units("layers", cfg.n_layers) + [dense_unit("head")]


def _block(cfg: ArchConfig, cos, sin):
    def step(h, p):
        h = h + L.gqa_attention(p["attn"], L.rmsnorm(p["ln1"], h), cfg, cos, sin,
                                impl=cfg.attention_impl,
                                balanced=cfg.attention_balanced)
        hn = L.rmsnorm(p["ln2"], h)
        ff = moe_ffn_auto(p["moe"], hn, cfg)
        if cfg.dense_residual:
            ff = ff + L.swiglu(p["dense_mlp"], hn)  # arctic parallel dense path
        return h + ff
    return step


def apply(cfg: ArchConfig, params: PyTree, batch, cut: Optional[int] = None,
          compute_dtype=jnp.bfloat16, return_hidden: bool = False):
    h = constrain_layer_io(params["embed"]["tok"][batch["tokens"]].astype(compute_dtype))
    cos, sin = L.rope_frequencies(cfg.head_dim, h.shape[1], cfg.rope_theta)
    if cut is not None:
        h = jax.lax.stop_gradient(h)
    h = scan_layers(_block(cfg, cos, sin), params["layers"], h,
                    cut=cut, remat=cfg.remat == "layer")
    h = L.rmsnorm(params["head"]["final_norm"], h)
    if return_hidden:
        return h
    return (h @ params["head"]["w"].astype(h.dtype)).astype(jnp.float32)


def loss_fn(cfg: ArchConfig, params: PyTree, batch, cut: Optional[int] = None,
            compute_dtype=jnp.bfloat16):
    from repro.models.losses import chunked_next_token_xent
    h = apply(cfg, params, batch, cut=cut, compute_dtype=compute_dtype,
              return_hidden=True)
    return chunked_next_token_xent(h, params["head"]["w"], batch["labels"],
                                   chunk=cfg.ce_chunk or None)


def lomo_pieces(cfg: ArchConfig, compute_dtype=jnp.bfloat16):
    """Segmented forward for the fused-backward strategies.

    One MoE layer — router + experts (+ shared experts / dense residual) —
    is one piece: its whole gradient is consumed inside one reverse-scan
    iteration, and ``moe_ffn_auto`` keeps riding the shard_map
    expert-parallel path when a sharding context is active (the vjp of a
    shard_map is itself a shard_map, so the backward all-to-alls stay
    per-device sized)."""
    from repro.models.base import LomoPieces
    from repro.models.losses import chunked_next_token_xent

    def embed_init(embed_p, prev, batch):
        del prev
        h = embed_p["tok"][batch["tokens"]].astype(compute_dtype)
        return constrain_layer_io(h), None

    def block(layer_p, shared_p, side, h):
        del shared_p, side
        cos, sin = L.rope_frequencies(cfg.head_dim, h.shape[1], cfg.rope_theta)
        return constrain_layer_io(_block(cfg, cos, sin)(h, layer_p))

    def head_loss(head_p, embed_p, h, batch):
        del embed_p  # untied head
        h = L.rmsnorm(head_p["final_norm"], h)
        return chunked_next_token_xent(h, head_p["w"], batch["labels"],
                                       chunk=cfg.ce_chunk or None)

    return LomoPieces(
        stage_keys=("layers",),
        stage_fns=(block,),
        stage_inits=(embed_init,),
        head_loss_fn=head_loss,
        split=lambda params: (params["embed"], (params["layers"],), None,
                              params["head"]),
        merge=lambda ep, stages, sp, hp: {"embed": ep, "layers": stages[0],
                                          "head": hp},
    )


# ---------------------------------------------------------------- serving

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def decode_step(cfg: ArchConfig, params: PyTree, cache: PyTree, tokens,
                compute_dtype=jnp.bfloat16):
    h = params["embed"]["tok"][tokens].astype(compute_dtype)
    max_len = cache["k"].shape[2]
    cos, sin = L.rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta)
    pos = cache["pos"]

    def step(h, p, layer_cache):
        hn = L.rmsnorm(p["ln1"], h)
        o, ck, cv = L.gqa_decode_attention(p["attn"], hn, cfg, cos, sin,
                                           layer_cache["k"], layer_cache["v"], pos)
        h = h + o
        hn2 = L.rmsnorm(p["ln2"], h)
        ff = moe_ffn_exact(p["moe"], hn2, cfg)
        if cfg.dense_residual:
            ff = ff + L.swiglu(p["dense_mlp"], hn2)
        return h + ff, {"k": ck, "v": cv}

    h, new_kv = scan_layers_with_cache(step, params["layers"],
                                       {"k": cache["k"], "v": cache["v"]}, h)
    h = L.rmsnorm(params["head"]["final_norm"], h)
    logits = (h @ params["head"]["w"].astype(h.dtype)).astype(jnp.float32)
    return logits, {"k": new_kv["k"], "v": new_kv["v"], "pos": pos + 1}


def prefill(cfg: ArchConfig, params: PyTree, batch, cache: PyTree,
            compute_dtype=jnp.bfloat16):
    """Prompt pass filling the KV cache (attention part mirrors transformer)."""
    h = params["embed"]["tok"][batch["tokens"]].astype(compute_dtype)
    b, s, _ = h.shape
    cos, sin = L.rope_frequencies(cfg.head_dim, s, cfg.rope_theta)
    cache_dtype = cache["k"].dtype

    def scan_step(h, xs):
        p, _ = xs
        hn = L.rmsnorm(p["ln1"], h)
        q = (hn @ p["attn"]["wq"].astype(h.dtype)).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = (hn @ p["attn"]["wk"].astype(h.dtype)).reshape(b, s, cfg.kv_heads, cfg.head_dim)
        v = (hn @ p["attn"]["wv"].astype(h.dtype)).reshape(b, s, cfg.kv_heads, cfg.head_dim)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        entry = {"k": k.astype(cache_dtype), "v": v.astype(cache_dtype)}
        n_rep = cfg.n_heads // cfg.kv_heads
        o = L.chunked_causal_attention(q, L._repeat_kv(k, n_rep), L._repeat_kv(v, n_rep),
                                       cfg.block_q, cfg.block_k,
                                       balanced=cfg.attention_balanced)
        h = h + o.reshape(b, s, -1) @ p["attn"]["wo"].astype(h.dtype)
        hn2 = L.rmsnorm(p["ln2"], h)
        ff = moe_ffn_auto(p["moe"], hn2, cfg)
        if cfg.dense_residual:
            ff = ff + L.swiglu(p["dense_mlp"], hn2)
        return h + ff, entry

    h, entries = jax.lax.scan(scan_step, h, (params["layers"], jnp.arange(cfg.n_layers)))
    cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], entries["k"], 0, axis=2),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], entries["v"], 0, axis=2),
        "pos": jnp.asarray(s, jnp.int32),
    }
    hl = L.rmsnorm(params["head"]["final_norm"], h[:, -1:])
    return (hl @ params["head"]["w"].astype(hl.dtype)).astype(jnp.float32), cache
