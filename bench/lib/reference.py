"""Plain reference of a model trained by HiFT or FPFT, for every family.

A cell's family (``bench/families/<family>.py``) gives the model's forward
pass and loss; this module gives what every family shares: how the
reference computes (:class:`Numerics`, :func:`rms`: float32 at ``highest``
matmul precision), AdamW, and the first steps of HiFT and FPFT.  It
imports nothing of the program.  The weights come from the benchmark's
own seeded init (``lib.model.init_params``).

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

HiFT semantics followed: groups of one unit (the family's ``units``: the
embedding, one block of a stack, the head) visited bottom-up, sweep after
sweep; one AdamW bundle per group, created at its first visit and carried
to the next (its step count is the group's number of visits); under
Mixed^Hi the resident weights are bfloat16, the forward computes from
them, and the update runs on a float32 master made from them at the first
visit.  The backward cut below the active group changes which gradients
are computed, not their values, so the reference differentiates with
respect to the active group only.
"""
from __future__ import annotations

import functools
import json

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


def rms(x, scale, eps, act):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(act)


@functools.lru_cache(maxsize=None)
def _step_fn(fam, ckey: str, key, precision: str):
    """Jitted ``(params, tokens, idx) -> (loss, grads)``: the gradient of
    every weight (``key`` None), of block ``idx`` of the stacked ``key``,
    or of the whole top-level ``key``."""
    c = json.loads(ckey)
    nm = Numerics(precision)
    stacked = key in fam.stacked(c)

    def f(params, tokens, idx):
        # frozen weights stay in their stored dtype and are widened one
        # block at a time where they are used; the trained ones are float32
        f32 = lambda t: jax.tree.map(lambda x: x.astype(F32), t)
        if key is None:
            return jax.value_and_grad(
                lambda p: fam.loss(c, nm, p, tokens))(f32(params))
        act = f32(jax.tree.map(lambda x: x[idx], params[key]) if stacked
                  else params[key])
        return jax.value_and_grad(lambda a: fam.loss(
            c, nm, params, tokens, (key, idx if stacked else None), a))(act)

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


def named_norms(tree: dict, layer: int = None, stacked=()) -> dict:
    """``{path: norm}`` of a subtree of the parameters.  Leaves under a
    ``stacked`` key (a family's ``stacked``) get one name per block,
    ``<key>/<leaf>[i]``; a ``layer`` index names the leaves of a one-block
    subtree."""
    out = {}
    flat = M.flatten(tree)
    sliced = {p: x for p, x in flat.items() if p.split("/")[0] in stacked}
    other = {p: x for p, x in flat.items() if p not in sliced}
    for p, x in jax.device_get(_slice_norms(sliced)).items():
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


def named_samples(tree: dict, layer: int = None, stacked=()) -> dict:
    """``{path: elements}``: every ``ceil(n / SAMPLE)``-th element of each
    leaf (of each block of a stacked leaf), named as :func:`named_norms`
    names them."""
    out = {}
    for p, x in M.flatten(tree).items():
        if p.split("/")[0] in stacked:
            for i in range(x.shape[0]):
                out[f"{p}[{i}]"] = np.asarray(_sampler(x[i].size)(x[i]))
        else:
            name = f"{p}[{layer}]" if layer is not None else p
            out[name] = np.asarray(_sampler(x.size)(x))
    return out


def hift_units(cell) -> list:
    """``(key, index | None)`` of each HiFT group of the cell, in visit
    order: one unit a group, bottom-up."""
    w = cell.workload
    if w["strategy"] != "hift" or w.get("m") != 1 \
            or w.get("order") != "bottom2up":
        raise ValueError("the reference follows hift (m=1, bottom2up) and "
                         f"fpft; the cell asks {w['strategy']} "
                         f"m={w.get('m')} {w.get('order')}")
    return [tuple(u) for u in cell.family.units(cell.config)]


def sweep_length(cell) -> int:
    return 1 if cell.workload["strategy"] == "fpft" else len(hift_units(cell))


def first_steps(cell) -> int:
    """The steps set-up drives and the reference follows: a HiFT cell
    names whole sweeps (``first_sweeps``), an FPFT cell steps."""
    w = cell.workload
    if w["strategy"] != "hift":
        return int(w["first_steps"])
    sweeps = int(w["first_sweeps"])
    if not 1 <= sweeps <= 2:
        raise ValueError("the reference follows one or two HiFT sweeps, "
                         f"the cell asks {sweeps}")
    return sweeps * sweep_length(cell)


FAULTS = ("half_batch", "revisit_fresh", "revisit_unchanged")


def replay(cell, seed: int, *, precision: str = "fp32",
           fault: str = None) -> dict:
    """Follow the cell's first steps (:func:`first_steps`) from the seed.

    Returns ``{"loss": [...], "grad": {leaf: norm}, "grad_sample": {leaf:
    elements}, "change": {leaf: norm}}`` and under HiFT ``"moment":
    {leaf: norm}``: each step's loss, each trained leaf's first gradient
    (its norm and a fixed sample of its elements), the norm of its change
    over the steps followed, and of its first moment after them."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    fam, c, workload = cell.family, cell.config, cell.workload
    ckey = json.dumps(c, sort_keys=True)
    pdt = PARAM_DTYPE[workload["policy"]]
    lr = float(workload["lr"])
    gen = MarkovBatches(cell.traffic, c["vocab_size"], seed)
    steps = first_steps(cell)
    params = M.init_params(fam.param_shapes(c), seed, pdt)
    out = {"loss": [], "grad": {}, "grad_sample": {}, "change": {}}
    f32 = lambda t: jax.tree.map(lambda x: x.astype(F32), t)

    def tokens_at(t):
        toks = gen.batch_at(t)
        if fault == "half_batch":
            toks = toks[: toks.shape[0] // 2]
        return jnp.asarray(toks)

    if workload["strategy"] == "fpft":
        fn = _step_fn(fam, ckey, None, precision)
        stacked = fam.stacked(c)
        p0 = f32(params)
        master = p0
        m = jax.tree.map(jnp.zeros_like, master)
        v = jax.tree.map(jnp.zeros_like, master)
        for t in range(steps):
            loss, g = fn(jax.tree.map(lambda x: x.astype(pdt), master),
                         tokens_at(t), 0)
            out["loss"].append(float(loss))
            if t == 0:
                out["grad"] = named_norms(g, stacked=stacked)
                out["grad_sample"] = named_samples(g, stacked=stacked)
            master, m, v = _adamw_tree(master, g, m, v, t + 1, lr)
        out["change"] = named_norms(jax.tree.map(jnp.subtract, master, p0),
                                    stacked=stacked)
        return out

    # a group's bundle after its first visit follows from its start and
    # its first gradient (m, v zero before); both wait in host memory for
    # the revisit, since every group's bundle together outgrows the chip
    units = hift_units(cell)
    firsts = {}                  # group -> (start, first gradient)
    out["moment"] = {}
    for t in range(steps):
        gi = t % len(units)
        key, idx = units[gi]
        fn = _step_fn(fam, ckey, key, precision)
        loss, g = fn(params, tokens_at(t), 0 if idx is None else idx)
        out["loss"].append(float(loss))
        stored = (params[key] if idx is None
                  else jax.tree.map(lambda x: x[idx], params[key]))
        if gi not in firsts:
            firsts[gi] = jax.device_get((stored, g))
            out["grad"].update(named_norms({key: g}, idx))
            out["grad_sample"].update(named_samples({key: g}, idx))
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
            {key: jax.tree.map(jnp.subtract, master, f32(start))}, idx))
        out["moment"].update(named_norms({key: m}, idx))
        if idx is None:
            params = {**params, key: jax.tree.map(
                lambda x: x.astype(pdt), master)}
        else:
            params = {**params, key: jax.tree.map(
                lambda full, x: full.at[idx].set(x.astype(full.dtype)),
                params[key], master)}
        del master, m, bundle, start
    return out
