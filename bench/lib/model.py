"""The configuration as the program runs it, and the weights from a seed.

:func:`arch_config` maps a configuration file (Hugging Face key names) onto
the program's ``ArchConfig`` for that model and refuses a file the program
would run differently.  :func:`init_params` makes the weights on the device
in one jitted call, in the layout the program's dense transformer takes,
and is what the plain reference starts from too.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

# configuration-file key -> the program's ArchConfig field
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "kv_heads",
           "num_hidden_layers": "n_layers", "vocab_size": "vocab",
           "rope_theta": "rope_theta",
           "tie_word_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias"}


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def _program_rms_eps() -> float:
    """The epsilon the program's RMSNorm adds: its default, which no model
    of the program overrides (``ArchConfig`` has no such field)."""
    import inspect

    from repro.models import layers
    return inspect.signature(layers.rmsnorm).parameters["eps"].default


def arch_config(c: dict):
    """The program's ``ArchConfig``: its own configuration of the model
    named by ``program_config`` with every size taken from ``c``."""
    import importlib

    base = importlib.import_module(
        f"repro.configs.{c['program_config']}").CONFIG
    kw = {f: c[k] for k, f in _FIELDS.items() if k in c}
    kw["head_dim"] = head_dim(c)
    kw["qkv_bias"] = bool(c.get("qkv_bias", False))
    kw["rope_theta"] = float(c["rope_theta"])
    cfg = dataclasses.replace(base, **kw)
    eps = _program_rms_eps()
    if float(c["rms_norm_eps"]) != eps:
        raise ValueError(f"rms_norm_eps {c['rms_norm_eps']}: the program "
                         f"normalises with {eps} for every model")
    if (cfg.family, cfg.norm, cfg.mlp) != ("dense", "rmsnorm", "swiglu"):
        raise ValueError(f"{c['program_config']}: the program runs "
                         f"{cfg.family}/{cfg.norm}/{cfg.mlp}, the reference "
                         "is a dense RMSNorm/SwiGLU transformer")
    if cfg.vocab_padded != cfg.vocab:
        raise ValueError(f"vocab {cfg.vocab} is not a multiple of "
                         f"{cfg.vocab_pad_multiple}: the program would pad it")
    return cfg


def param_shapes(c: dict) -> dict:
    """``{path: (shape, init scale)}`` in the program's layout; a scale of
    ``None`` is a norm scale (ones), ``0`` a bias (zeros)."""
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


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, x in flat.items():
        *parents, leaf = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number, 64-bit seeds included."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_params(c: dict, seed: int, dtype=jnp.float32) -> dict:
    """Seeded weights, made on the device by one jitted call and stored in
    ``dtype`` (drawn in float32, then rounded)."""
    shapes = param_shapes(c)

    def make(key):
        flat = {}
        for i, (path, (shape, scale)) in enumerate(sorted(shapes.items())):
            if scale is None:
                x = jnp.ones(shape, jnp.float32)
            elif scale == 0:
                x = jnp.zeros(shape, jnp.float32)
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * scale
            flat[path] = x.astype(dtype)
        return nest(flat)

    return jax.jit(make)(seed_key(seed))
