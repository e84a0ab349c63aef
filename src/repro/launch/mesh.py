"""Production mesh builders.

Single-pod: (data=16, model=16) = 256 chips (one TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis carries
cross-pod data parallelism over DCI while ``data``/``model`` stay inside a
pod on ICI.

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before the first jax call.

:func:`mesh_from_spec` is the user-facing builder behind the launcher's
``--mesh`` flag: ``"2x4"`` (data x model) or ``"data=2,model=4"`` both give
a (data=2, model=4) mesh over the first 8 visible devices.  On a CPU-only
host, ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` fabricates N
host devices so every sharded code path runs (and is tested) without
accelerators — ``run.sh`` exports 8 by default.
"""
from __future__ import annotations

import jax
import numpy as np


def init_distributed(coordinator: str, num_processes: int, process_id: int,
                     *, local_device_count: int | None = None) -> None:
    """Join a ``jax.distributed`` coordination service — the multi-process
    launch path (``launch.train --coordinator host:port --num-processes N
    --process-id i``).  Every process runs the SAME program; after this call
    ``jax.devices()`` is the GLOBAL device list, so ``mesh_from_spec``
    builds one mesh spanning all processes and the strategies' sharded
    steps run multi-controller SPMD unchanged.

    Must run before anything touches the jax backend:

    - ``local_device_count`` fabricates that many host CPU devices per
      process via ``XLA_FLAGS`` (the multi-host CI harness runs 4 processes
      x 1 local device = one 4-device global mesh on a laptop; one device
      per process keeps each node's gloo collective issue order equal to
      program order — multiple local devices race their rank threads on
      the shared communicator and can cross messages).
    - On CPU backends the default cross-process collectives implementation
      refuses multi-process computations outright; this selects the gloo
      transport (the same one ``jax[cpu]`` ships for exactly this purpose).
      Harmless on TPU/GPU, where collectives ride ICI/NCCL.
    """
    import os

    if local_device_count:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{int(local_device_count)}").strip()
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke tests/benches (keeps the same axis names)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that carry the batch dimension (pod folds into data)."""
    from repro.dist.shardings import data_axes as _impl
    return _impl(mesh)


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """Parse a ``--mesh`` value into ``{axis: size}`` (ordered).

    Accepted forms:
      - ``"2x4"``            -> {"data": 2, "model": 4}
      - ``"data=2,model=4"`` -> {"data": 2, "model": 4} (any axis names)
    Sizes must be positive integers; no device-count check happens here.
    """
    spec = spec.strip()
    if not spec:
        raise ValueError("empty mesh spec")
    if "=" in spec:
        axes: dict[str, int] = {}
        for part in spec.split(","):
            name, _, size = part.partition("=")
            name = name.strip()
            if not name or name in axes:
                raise ValueError(f"bad mesh spec {spec!r}: axis {name!r}")
            axes[name] = int(size)
    else:
        sizes = [int(s) for s in spec.replace(",", "x").split("x")]
        if len(sizes) != 2:
            raise ValueError(
                f"bad mesh spec {spec!r}: want DxM (e.g. 2x4) or name=size pairs")
        axes = {"data": sizes[0], "model": sizes[1]}
    if any(s < 1 for s in axes.values()):
        raise ValueError(f"bad mesh spec {spec!r}: sizes must be >= 1")
    return axes


def mesh_from_spec(spec: str, devices=None):
    """Build a Mesh from a ``--mesh`` spec over the first prod(sizes) visible
    devices (so a 2x2 mesh works on an 8-device host).  Raises if the host
    does not expose enough devices — on CPU, raise the count with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    axes = parse_mesh_spec(spec)
    shape = tuple(axes.values())
    need = int(np.prod(shape))
    devices = list(jax.devices() if devices is None else devices)
    if need > len(devices):
        raise ValueError(
            f"mesh {spec!r} needs {need} devices but only {len(devices)} are "
            f"visible; set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} (run.sh exports 8 by default)")
    grid = np.asarray(devices[:need]).reshape(shape)
    return jax.sharding.Mesh(grid, tuple(axes))
