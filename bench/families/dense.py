"""The dense GQA transformer (the program's ``models/transformer.py``), as
``bench.lib.spec.family`` asks of a family: written from the published
description (RMSNorm, rotary embeddings on halves of each head, GQA with
optional q/k/v biases, SwiGLU, tied or untied head, next-token loss)."""
from __future__ import annotations

import dataclasses
import importlib
import inspect
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.reference import F32, rms

# configuration-file key -> the program's ArchConfig field
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "kv_heads",
           "num_hidden_layers": "n_layers", "vocab_size": "vocab",
           "rope_theta": "rope_theta",
           "tie_word_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias"}


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def arch_config(c: dict):
    """The program's ``ArchConfig``: its own configuration of the model
    named by ``program_config`` with every size taken from ``c``.  The
    RMSNorm epsilon is the file's where ``ArchConfig`` has a field for it;
    otherwise a file that states another epsilon than the program's fixed
    one is refused."""
    base = importlib.import_module(
        f"repro.configs.{c['program_config']}").CONFIG
    kw = {f: c[k] for k, f in _FIELDS.items() if k in c}
    kw["head_dim"] = head_dim(c)
    kw["qkv_bias"] = bool(c.get("qkv_bias", False))
    kw["rope_theta"] = float(c["rope_theta"])
    if any(f.name == "rms_norm_eps" for f in dataclasses.fields(base)):
        kw["rms_norm_eps"] = float(c["rms_norm_eps"])
    else:                       # the default of the program's rmsnorm
        from repro.models import layers
        eps = inspect.signature(layers.rmsnorm).parameters["eps"].default
        if float(c["rms_norm_eps"]) != eps:
            raise ValueError(f"rms_norm_eps {c['rms_norm_eps']}: the "
                             f"program normalises with {eps} for every model")
    cfg = dataclasses.replace(base, **kw)
    if (cfg.family, cfg.norm, cfg.mlp) != ("dense", "rmsnorm", "swiglu"):
        raise ValueError(f"{c['program_config']}: the program runs "
                         f"{cfg.family}/{cfg.norm}/{cfg.mlp}, the reference "
                         "is a dense RMSNorm/SwiGLU transformer")
    if cfg.vocab_padded != cfg.vocab:
        raise ValueError(f"vocab {cfg.vocab} is not a multiple of "
                         f"{cfg.vocab_pad_multiple}: the program would pad it")
    return cfg


def param_shapes(c: dict) -> dict:
    d, h, kv = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    hd, ff, n, v = (head_dim(c), c["intermediate_size"],
                    c["num_hidden_layers"], c["vocab_size"])
    s = {"embed/tok": ((v, d), 0.02),
         "layers/ln1/scale": ((n, d), None),
         "layers/attn/wq": ((n, d, h * hd), 1 / math.sqrt(d)),
         "layers/attn/wk": ((n, d, kv * hd), 1 / math.sqrt(d)),
         "layers/attn/wv": ((n, d, kv * hd), 1 / math.sqrt(d)),
         "layers/attn/wo": ((n, h * hd, d), 1 / math.sqrt(h * hd)),
         "layers/ln2/scale": ((n, d), None),
         "layers/mlp/w_gate": ((n, d, ff), 1 / math.sqrt(d)),
         "layers/mlp/w_up": ((n, d, ff), 1 / math.sqrt(d)),
         "layers/mlp/w_down": ((n, ff, d), 1 / math.sqrt(ff)),
         "head/final_norm/scale": ((d,), None)}
    if c.get("qkv_bias"):
        s["layers/attn/bq"] = ((n, h * hd), 0)
        s["layers/attn/bk"] = ((n, kv * hd), 0)
        s["layers/attn/bv"] = ((n, kv * hd), 0)
    if not c.get("tie_word_embeddings"):
        s["head/w"] = ((d, v), 1 / math.sqrt(d))
    return s


def units(c: dict) -> list:
    return ([("embed", None)]
            + [("layers", i) for i in range(c["num_hidden_layers"])]
            + [("head", None)])


def stacked(c: dict) -> tuple:
    return ("layers",)


# ---------------------------------------------------------------- reference

def _rope(x, theta):
    """Rotary embedding on the two halves of each head: (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x32 = x.astype(F32)
    x1, x2 = x32[..., :hd // 2], x32[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _block(c, nm, h, p):
    b, s, _ = h.shape
    nh, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        head_dim(c)
    eps = c["rms_norm_eps"]
    a = rms(h, p["ln1"]["scale"], eps, nm.act)
    q = nm.dot("bsd,de->bse", a, p["attn"]["wq"]).astype(nm.act)
    k = nm.dot("bsd,de->bse", a, p["attn"]["wk"]).astype(nm.act)
    v = nm.dot("bsd,de->bse", a, p["attn"]["wv"]).astype(nm.act)
    if "bq" in p["attn"]:
        q = q + p["attn"]["bq"].astype(nm.act)
        k = k + p["attn"]["bk"].astype(nm.act)
        v = v + p["attn"]["bv"].astype(nm.act)
    q = _rope(q.reshape(b, s, nh, hd), c["rope_theta"])
    k = _rope(k.reshape(b, s, kv, hd), c["rope_theta"])
    v = v.reshape(b, s, kv, hd)
    k = jnp.repeat(k, nh // kv, axis=2)     # query head i reads kv head i // r
    v = jnp.repeat(v, nh // kv, axis=2)
    sc = nm.dot("bqhd,bkhd->bhqk", q, k).astype(F32) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1).astype(nm.act)
    o = nm.dot("bhqk,bkhd->bqhd", pr, v).astype(nm.act).reshape(b, s, nh * hd)
    h = h + nm.dot("bse,ed->bsd", o, p["attn"]["wo"]).astype(nm.act)
    a = rms(h, p["ln2"]["scale"], eps, nm.act)
    g = jax.nn.silu(nm.dot("bsd,df->bsf", a, p["mlp"]["w_gate"]).astype(F32))
    u = nm.dot("bsd,df->bsf", a, p["mlp"]["w_up"]).astype(F32)
    gu = (g * u).astype(nm.act)
    return h + nm.dot("bsf,fd->bsd", gu, p["mlp"]["w_down"]).astype(nm.act)


def _xent(c, nm, h, params, tokens):
    """Mean next-token cross entropy, one batch row at a time."""
    w = (params["embed"]["tok"].T if c.get("tie_word_embeddings")
         else params["head"]["w"])
    hf = rms(h, params["head"]["final_norm"]["scale"], c["rms_norm_eps"],
             nm.act)

    @jax.checkpoint
    def row(hr, tr):
        logits = nm.dot("sd,dv->sv", hr[:-1], w).astype(F32)
        gold = jnp.take_along_axis(logits, tr[1:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    tot = jax.lax.map(lambda xs: row(*xs), (hf, tokens))
    return jnp.sum(tot) / (tokens.shape[0] * (tokens.shape[1] - 1))


def _loss(c, nm, params, tokens, idx=None, active=None):
    """Loss of ``params``.  With ``idx``, block ``idx`` computes from the
    differentiated ``active`` weights and every other block is a constant:
    ``idx`` below 0 trains the embedding, at the depth the head."""
    h = params["embed"]["tok"][tokens].astype(nm.act)
    layers = params["layers"]
    n = c["num_hidden_layers"]
    layer = lambda hh, p: _block(c, nm, hh, p)

    if idx is None:
        def body(hh, lp):
            return layer(hh, lp), None
        xs = layers
    else:
        def body(hh, xs):
            lp, i = xs
            branches = [
                lambda x: jax.lax.stop_gradient(
                    layer(jax.lax.stop_gradient(x), lp)),
                lambda x: layer(jax.lax.stop_gradient(x), active),
                lambda x: layer(x, lp)]
            which = jnp.where(i < idx, 0, jnp.where(i == idx, 1, 2))
            return jax.lax.switch(which, branches, hh), None
        xs = (jax.lax.stop_gradient(layers), jnp.arange(n))
        if active is None:          # the embedding or the head trains
            active = jax.tree.map(lambda x: x[0], xs[0])
    h, _ = jax.lax.scan(jax.checkpoint(body), h, xs)
    return _xent(c, nm, h, params, tokens)


def loss(c, nm, params, tokens, unit=None, active=None):
    if unit is None:
        return _loss(c, nm, params, tokens)
    key, i = unit
    if key == "layers":
        return _loss(c, nm, params, tokens, i, active)
    where = -1 if key == "embed" else c["num_hidden_layers"]
    return _loss(c, nm, {**params, key: active}, tokens, where)


# -------------------------------------------------------------------- FLOPs
# Operations that a training step requires, from shapes alone: the forward
# pass; activation gradients only for the layers at or above the backward
# cut; weight gradients only for the group being trained; causal attention
# (half the score matrix); no recomputation under remat.  An embedding
# lookup and its gradient are gathers and scatters: no matmul operations.
# A multiply-add counts as 2 operations.

def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "h": h, "kv": c["num_key_value_heads"],
            "hd": head_dim(c), "ff": c["intermediate_size"],
            "L": c["num_hidden_layers"], "V": c["vocab_size"],
            "tied": bool(c.get("tie_word_embeddings", False))}


def layer_matmul_params(c: dict) -> int:
    """Weights one token meets in one block's matmuls: q, k, v, o and the
    three SwiGLU projections."""
    x = dims(c)
    return (x["d"] * x["h"] * x["hd"] * 2 + x["d"] * x["kv"] * x["hd"] * 2
            + 3 * x["d"] * x["ff"])


def attention_forward(c: dict, batch: int, seq: int) -> int:
    """One layer's causal attention forward: QK^T and PV over the
    seq * (seq + 1) / 2 query-key pairs a causal mask keeps."""
    x = dims(c)
    return 4 * batch * x["h"] * x["hd"] * seq * (seq + 1) // 2


def step_flops(c: dict, batch: int, seq: int, group=None) -> int:
    x = dims(c)
    t = batch * seq
    group = units(c) if group is None else list(group)
    keys = {k for k, _ in group}
    p_l = layer_matmul_params(c)
    attn = attention_forward(c, batch, seq)
    head_mm = 2 * x["d"] * x["V"] * t
    layer_mm = 2 * p_l * t
    forward = x["L"] * (layer_mm + attn) + head_mm
    if "embed" in keys:
        cut = 0
    else:
        layers = [i for k, i in group if k == "layers"]
        cut = min(layers) if layers else x["L"]
    # the loss's gradient always reaches the final hidden state
    act = head_mm + (x["L"] - cut) * (layer_mm + 2 * attn)
    weight = sum(layer_mm for k, _ in group if k == "layers")
    head_weight_trained = ("embed" in keys) if x["tied"] else ("head" in keys)
    if head_weight_trained:
        weight += head_mm
    return forward + act + weight
