"""Plain reference of a dense GQA transformer trained by HiFT or FPFT.

Written from the published description (RMSNorm, rotary embeddings on
halves of each head, grouped-query attention, SwiGLU, next-token cross
entropy) in ``jax.numpy``, in float32 at ``highest`` matmul precision.  It
imports nothing of the program.  The weights come from the benchmark's own
seeded init (``lib.model.init_params``).

:func:`replay` follows the first steps of a cell from the seed and returns
what the correctness check compares: each step's loss, the norm of each
trained leaf's first gradient, the norm of each trained leaf's change, and
under HiFT the norm of each leaf's first moment after its last visit.
``precision="fp8"`` computes the same steps lower (the control): matmul
operands rounded to float8 (e4m3, one scale per tensor), activations in
bfloat16.  ``fault`` plants one of the faults the check has to catch:
``"half_batch"`` leaves out half of each batch; ``"revisit_fresh"`` starts
every HiFT revisit from a fresh bundle, as if the offloaded one were lost;
``"revisit_unchanged"`` leaves weights and bundle unchanged at a revisit.

HiFT semantics followed: groups of one unit (embedding, one block, head)
visited bottom-up, sweep after sweep; one AdamW bundle per group, created
at its first visit and carried to the next (its step count is the group's
number of visits); under Mixed^Hi the resident weights are bfloat16, the
forward computes from them, and the update runs on a float32 master made
from them at the first visit.  The backward cut below the active group
changes which gradients are computed, not their values, so the reference
differentiates with respect to the active group only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import model as M
from bench.lib.traffic import MarkovBatches

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
ADAMW = {"b1": 0.9, "b2": 0.999, "eps": 1e-8}
# the control's precision: one step below the matmul precision a cell
# states (its workload's "matmuls")
CONTROL = {"bf16": "fp8"}
PARAM_DTYPE = {"fp32": F32, "mixed_hi": BF16}


def _fp8(x):
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return (q * scale).astype(BF16)


class Numerics:
    """How the reference computes: ``act`` is the activations' dtype and
    :meth:`dot` every matmul."""

    def __init__(self, precision: str):
        if precision not in ("fp32", "fp8"):
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.act = F32 if precision == "fp32" else BF16

    def dot(self, spec: str, a, b):
        if self.precision == "fp32":
            return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                              precision=HIGHEST)
        return jnp.einsum(spec, _fp8(a), _fp8(b))


def _rms(x, scale, eps, act):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(act)


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
        M.head_dim(c)
    eps = c["rms_norm_eps"]
    a = _rms(h, p["ln1"]["scale"], eps, nm.act)
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
    a = _rms(h, p["ln2"]["scale"], eps, nm.act)
    g = jax.nn.silu(nm.dot("bsd,df->bsf", a, p["mlp"]["w_gate"]).astype(F32))
    u = nm.dot("bsd,df->bsf", a, p["mlp"]["w_up"]).astype(F32)
    gu = (g * u).astype(nm.act)
    return h + nm.dot("bsf,fd->bsd", gu, p["mlp"]["w_down"]).astype(nm.act)


def _xent(c, nm, h, params, tokens):
    """Mean next-token cross entropy, one batch row at a time."""
    w = (params["embed"]["tok"].T if c.get("tie_word_embeddings")
         else params["head"]["w"])
    hf = _rms(h, params["head"]["final_norm"]["scale"], c["rms_norm_eps"],
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


@functools.lru_cache(maxsize=None)
def _step_fn(ckey: str, kind: str, precision: str):
    """Jitted ``(params, tokens, idx) -> (loss, grads)`` for one kind of
    group: ``embed``, ``layer`` (block ``idx``), ``head`` or ``all``."""
    import json
    c = json.loads(ckey)
    nm = Numerics(precision)
    n = c["num_hidden_layers"]

    def f(params, tokens, idx):
        # frozen weights stay in their stored dtype and are widened one
        # block at a time where they are used; the trained ones are float32
        f32 = lambda t: jax.tree.map(lambda x: x.astype(F32), t)
        if kind == "all":
            return jax.value_and_grad(
                lambda p: _loss(c, nm, p, tokens))(f32(params))
        if kind == "layer":
            act = f32(jax.tree.map(lambda x: x[idx], params["layers"]))
            return jax.value_and_grad(
                lambda a: _loss(c, nm, params, tokens, idx, a))(act)
        key = "embed" if kind == "embed" else "head"
        where = -1 if kind == "embed" else n

        def lf(a):
            return _loss(c, nm, {**params, key: a}, tokens, where, None)
        return jax.value_and_grad(lf)(f32(params[key]))

    return jax.jit(f)


def _adamw(master, g, m, v, count, lr):
    b1, b2, eps = ADAMW["b1"], ADAMW["b2"], ADAMW["eps"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat, vhat = m / (1 - b1 ** count), v / (1 - b2 ** count)
    return master - lr * (mhat / (jnp.sqrt(vhat) + eps)), m, v


_adamw_tree = jax.jit(lambda p, g, m, v, count, lr: jax.tree.transpose(
    jax.tree.structure(p), jax.tree.structure((0, 0, 0)),
    jax.tree.map(lambda a, b, c_, d: _adamw(a, b, c_, d, count, lr),
                 p, g, m, v)))


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32)))), tree)


@jax.jit
def _slice_norms(tree):
    """Norm of each leading-dim slice of each stacked leaf."""
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32)).reshape(x.shape[0], -1), axis=1)), tree)


def named_norms(tree: dict, layer: int = None) -> dict:
    """``{path: norm}`` of a subtree of the parameters.  Stacked leaves
    under ``layers`` get one name per block, ``layers/<leaf>[i]``; a
    ``layer`` index names the blocks of a one-block subtree."""
    out = {}
    flat = M.flatten(tree)
    stacked = {p: x for p, x in flat.items()
               if p.startswith("layers/") and layer is None}
    other = {p: x for p, x in flat.items() if p not in stacked}
    for p, x in jax.device_get(_slice_norms(stacked)).items():
        for i, val in enumerate(np.asarray(x)):
            out[f"{p}[{i}]"] = float(val)
    for p, x in jax.device_get(_leaf_norms(other)).items():
        out[f"{p}[{layer}]" if layer is not None else p] = float(x)
    return out


SAMPLE = 1 << 20      # elements kept of each leaf for the gradient gap


@functools.lru_cache(maxsize=None)
def _sampler(n: int):
    stride = -(-n // SAMPLE)
    return jax.jit(lambda x: x.reshape(-1)[::stride].astype(F32))


def named_samples(tree: dict, layer: int = None) -> dict:
    """``{path: elements}``: every ``ceil(n / SAMPLE)``-th element of each
    leaf (of each block of a stacked leaf), named as :func:`named_norms`
    names them."""
    out = {}
    for p, x in M.flatten(tree).items():
        if p.startswith("layers/") and layer is None:
            for i in range(x.shape[0]):
                out[f"{p}[{i}]"] = np.asarray(_sampler(x[i].size)(x[i]))
        else:
            name = f"{p}[{layer}]" if layer is not None else p
            out[name] = np.asarray(_sampler(x.size)(x))
    return out


def _group_kinds(c: dict, workload: dict) -> list:
    """``(kind, idx, key)`` for each group of the cell, in visit order."""
    n = c["num_hidden_layers"]
    if workload["strategy"] == "fpft":
        return [("all", 0, None)]
    if workload["strategy"] != "hift" or workload.get("m") != 1 \
            or workload.get("order") != "bottom2up":
        raise ValueError("the reference follows hift (m=1, bottom2up) and "
                         f"fpft; the cell asks {workload['strategy']} "
                         f"m={workload.get('m')} {workload.get('order')}")
    return ([("embed", -1, "embed")] + [("layer", i, "layers")
                                        for i in range(n)]
            + [("head", n, "head")])


def sweep_length(c: dict, workload: dict) -> int:
    return len(_group_kinds(c, workload))


def first_steps(c: dict, workload: dict) -> int:
    """The steps set-up drives and the reference follows: a HiFT cell
    names whole sweeps (``first_sweeps``), an FPFT cell steps."""
    if workload["strategy"] != "hift":
        return int(workload["first_steps"])
    sweeps = int(workload["first_sweeps"])
    if not 1 <= sweeps <= 2:
        raise ValueError("the reference follows one or two HiFT sweeps, "
                         f"the cell asks {sweeps}")
    return sweeps * sweep_length(c, workload)


FAULTS = ("half_batch", "revisit_fresh", "revisit_unchanged")


def replay(c: dict, workload: dict, mix: dict, seed: int, *,
           precision: str = "fp32", fault: str = None) -> dict:
    """Follow the cell's first steps (:func:`first_steps`) from the seed.

    Returns ``{"loss": [...], "grad": {leaf: norm}, "grad_sample": {leaf:
    elements}, "change": {leaf: norm}}`` and under HiFT ``"moment":
    {leaf: norm}``: each step's loss, each trained leaf's first gradient
    (its norm and a fixed sample of its elements), the norm of its change
    over the steps followed, and of its first moment after them."""
    import json

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ckey = json.dumps(c, sort_keys=True)
    policy = workload["policy"]
    pdt = PARAM_DTYPE[policy]
    lr = float(workload["lr"])
    gen = MarkovBatches(mix, c["vocab_size"], seed)
    steps = first_steps(c, workload)
    kinds = _group_kinds(c, workload)
    params = M.init_params(c, seed, pdt)
    out = {"loss": [], "grad": {}, "grad_sample": {}, "change": {}}
    f32 = lambda t: jax.tree.map(lambda x: x.astype(F32), t)

    def tokens_at(t):
        toks = gen.batch_at(t)
        if fault == "half_batch":
            toks = toks[: toks.shape[0] // 2]
        return jnp.asarray(toks)

    if workload["strategy"] == "fpft":
        fn = _step_fn(ckey, "all", precision)
        p0 = f32(params)
        master = p0
        m = jax.tree.map(jnp.zeros_like, master)
        v = jax.tree.map(jnp.zeros_like, master)
        for t in range(steps):
            loss, g = fn(jax.tree.map(lambda x: x.astype(pdt), master),
                         tokens_at(t), 0)
            out["loss"].append(float(loss))
            if t == 0:
                out["grad"] = named_norms(g)
                out["grad_sample"] = named_samples(g)
            master, m, v = _adamw_tree(master, g, m, v, t + 1, lr)
        out["change"] = named_norms(jax.tree.map(jnp.subtract, master, p0))
        return out

    # a group's bundle after its first visit follows from its start and
    # its first gradient (m, v zero before); both wait in host memory for
    # the revisit, since every group's bundle together outgrows the chip
    firsts = {}                  # group -> (start, first gradient)
    out["moment"] = {}
    for t in range(steps):
        gi = t % len(kinds)
        kind, idx, key = kinds[gi]
        fn = _step_fn(ckey, kind, precision)
        loss, g = fn(params, tokens_at(t), idx)
        out["loss"].append(float(loss))
        layer = idx if kind == "layer" else None
        stored = (jax.tree.map(lambda x: x[idx], params["layers"])
                  if kind == "layer" else params[key])
        if gi not in firsts:
            firsts[gi] = jax.device_get((stored, g))
            out["grad"].update(named_norms({key: g}, layer))
            out["grad_sample"].update(named_samples({key: g}, layer))
            start, bundle = stored, None
        elif fault == "revisit_unchanged":
            continue
        elif fault == "revisit_fresh":
            start, bundle = jax.device_put(firsts[gi][0]), None
        else:
            start, g1 = jax.device_put(firsts[gi])
            zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), start)
            bundle = (*_adamw_tree(f32(start), g1, zeros, zeros, 1, lr), 1)
            del g1
        if bundle is None:       # a first visit, or a lost bundle
            zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), stored)
            bundle = (f32(stored), zeros, zeros, 0)
        master, m, v, count = bundle
        master, m, _ = _adamw_tree(master, g, m, v, count + 1, lr)
        out["change"].update(named_norms(
            {key: jax.tree.map(jnp.subtract, master, f32(start))}, layer))
        out["moment"].update(named_norms({key: m}, layer))
        if kind == "layer":
            params = {**params, "layers": jax.tree.map(
                lambda full, x: full.at[idx].set(x.astype(full.dtype)),
                params["layers"], master)}
        else:
            params = {**params, key: jax.tree.map(
                lambda x: x.astype(pdt), master)}
        del master, m, bundle, start
    return out
