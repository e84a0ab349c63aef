"""The weights from a seed, and HiFT's groups, for every family.

:func:`init_params` makes the weights on the device in one jitted call, in
the layout a family's ``param_shapes`` gives (the program's), and is what
the plain reference starts from too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


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


def init_params(shapes: dict, seed: int, dtype=jnp.float32) -> dict:
    """Seeded weights of ``shapes`` (a family's ``param_shapes``: a scale of
    ``None`` is a norm scale, ones; ``0`` a bias, zeros), made on the
    device by one jitted call and stored in ``dtype`` (drawn in float32,
    then rounded)."""
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


def groups(units: list, m: int) -> list:
    """Contiguous groups of ``m`` units (HiFT's partition)."""
    return [units[i:i + m] for i in range(0, len(units), m)]
