#!/usr/bin/env python3
"""Smoke run of HiFT training and serving on TPU chips, through the entry
points a user calls, at the full published width of the model.

    python chip_smoke.py              # one chip: qwen2-0.5b train + serve
    python chip_smoke.py --chips 4    # four chips: internlm2-1.8b mesh phase

One chip runs three phases:

- device: what JAX reports about the chip;
- train:  ``repro.launch.train.main`` with HiFT + AdamW (fp32) for one full
  sweep of the k groups plus a revisit of group 0, which brings that
  group's optimizer bundle back from pinned host memory;
- serve:  ``ContinuousServeEngine.from_train_state`` on the trained state,
  4 requests of mixed prompt lengths.

``--chips 4`` runs only the mesh phase: ``fpft`` and ``hift`` on a 1x4
(data x model) mesh, compared with ``hift`` unsharded on the first device.

Every phase raises on a failed check, so the script exits non-zero; it also
exits non-zero before any phase when JAX finds no TPU.  The last line of
standard output is one JSON object: ``{"ok": true, "device": {...}}``.
Lines before it are informational.  JAX's compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_ARCH = "qwen2_0_5b"
TRAIN_M = 6                 # 26 units (embed, 24 layers, head) -> 5 groups
TRAIN_BATCH, TRAIN_SEQ = 8, 512
SERVE_PROMPT_LENS = (5, 17, 33, 60)
SERVE_MAX_NEW = 16

MESH_ARCH = "internlm2_1_8b"
MESH_SPEC = "1x4"
MESH_STEPS = 3
# fp32 does not fit one chip: the resident tree (7.0 GiB) plus the per-step
# copy of the frozen layer stack exceeds its HBM, so the mesh phase runs the
# paper's Mixed^Hi policy (bf16 resident tree, fp32 master for the active
# group only).  Top-down, the first steps train the head and the top layers:
# a bottom group's backward computes weight gradients for the whole stack
# above it, which with that copy does not fit one chip either.
MESH_POLICY = "mixed_hi"
MESH_M = 1
MESH_ORDER = "top2down"
MESH_BATCH, MESH_SEQ = 8, 512
# stated before the first four-chip run: bf16 compute under different
# shardings reorders reductions, so losses agree to a relative tolerance
HIFT_SHARDED_RTOL = 1e-2
FPFT_STEP1_RTOL = 2e-3


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _peak_bytes() -> list:
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


# ------------------------------------------------------------------ device

def device_phase(chips: int) -> dict:
    """Refuse anything but a TPU with at least ``chips`` devices; print what
    JAX reports about it."""
    import importlib.metadata

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} TPU chips, "
                         f"JAX found {len(devs)}")
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"bytes_limit={limit}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------- train

def train_phase(*, smoke: bool = False, m: int = TRAIN_M,
                batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
                extra: tuple = ()) -> dict:
    """Run ``launch.train`` for k + 1 HiFT steps and return its losses, step
    times and final state, plus the interpret flag each fused AdamW kernel
    launch was traced with."""
    from repro.configs.registry import get_config
    from repro.core.grouping import make_groups
    from repro.kernels import fused_adamw
    from repro.kernels.ops import default_interpret
    from repro.launch import train as launch_train
    from repro.models import get_family

    cfg = get_config(TRAIN_ARCH, smoke=smoke)
    k = len(make_groups(get_family(cfg).unit_spec(cfg), m))
    argv = ["--arch", TRAIN_ARCH, "--strategy", "hift", "--optimizer", "adamw",
            "--policy", "fp32", "--m", str(m), "--steps", str(k + 1),
            "--batch", str(batch), "--seq", str(seq), *extra]
    if smoke:
        argv.append("--smoke")

    launches = []
    real_call = fused_adamw.elementwise_update_call

    def recording_call(*args, interpret=None, **kwargs):
        launches.append(default_interpret(interpret))
        return real_call(*args, interpret=interpret, **kwargs)

    fused_adamw.elementwise_update_call = recording_call
    try:
        out = launch_train.main(argv)
    finally:
        fused_adamw.elementwise_update_call = real_call
    return {"cfg": cfg, "k": k, "m": m, "batch": batch, "seq": seq,
            "losses": out["losses"],
            "step_times": out["step_times"], "state": out["state"],
            "fused_interpret": launches}


def check_train(run: dict, *, offload_kind: str = "pinned_host",
                fused_interpret: bool = False) -> None:
    """k + 1 finite losses; every fused AdamW launch traced with the
    expected interpret flag (compiled on the chip); every group's bundle,
    all inactive after the last step, in ``offload_kind`` memory."""
    k, losses = run["k"], run["losses"]
    _require(len(losses) == k + 1, f"{len(losses)} losses for k + 1 = {k + 1}")
    _require(all(math.isfinite(x) for x in losses), f"losses {losses}")
    launches = run["fused_interpret"]
    _require(bool(launches), "the fused AdamW kernel never ran")
    _require(all(i == fused_interpret for i in launches),
             f"fused AdamW interpret flags {launches}, "
             f"expected all {fused_interpret}")
    import jax
    bundles = run["state"].opt_state
    _require(sorted(bundles, key=int) == [str(g) for g in range(k)],
             f"bundles {sorted(bundles)} for k = {k}")
    kinds = {leaf.sharding.memory_kind
             for b in bundles.values() for leaf in jax.tree.leaves(b)}
    _require(kinds == {offload_kind},
             f"inactive bundles in {kinds}, expected {offload_kind}")


# ------------------------------------------------------------------- serve

def serve_phase(cfg, state, *, prompt_lens: tuple = SERVE_PROMPT_LENS,
                max_new: int = SERVE_MAX_NEW, seed: int = 0) -> dict:
    """Serve the trained state with continuous batching; the fixed-batch
    ``ServeEngine`` greedy output for the same prompts is the reference."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.engine import ContinuousServeEngine, ServeEngine
    from repro.serve.scheduler import ServeRequest

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in prompt_lens]
    engine = ContinuousServeEngine.from_train_state(cfg, state,
                                                    slots=len(prompts))
    reqs = [ServeRequest(prompt=p, max_new_tokens=max_new) for p in prompts]
    engine.run(reqs)
    ref = ServeEngine.from_train_state(
        cfg, state, batch=len(prompts),
        max_len=max(prompt_lens) + max_new).generate(
            [jnp.asarray(p, jnp.int32) for p in prompts],
            max_new_tokens=max_new)
    return {"outs": [r.out_tokens for r in reqs], "ref": ref,
            "max_new": max_new, "occupancy": engine.cache.occupancy(),
            "decode_steps": engine.steps}


def check_serve(res: dict) -> None:
    """Every request got its tokens and the paged cache drained."""
    for i, out in enumerate(res["outs"]):
        _require(len(out) == res["max_new"],
                 f"request {i} returned {len(out)} of {res['max_new']} tokens")
    _require(res["occupancy"] == 0.0,
             f"paged cache occupancy {res['occupancy']} after the run")


def agreement(res: dict) -> tuple[int, int]:
    """(tokens equal to the reference at the same position, tokens)."""
    same = sum(a == b for out, ref in zip(res["outs"], res["ref"])
               for a, b in zip(out, ref))
    return same, sum(len(out) for out in res["outs"])


# -------------------------------------------------------------------- mesh

def mesh_phase(*, smoke: bool = False, batch: int = MESH_BATCH,
               seq: int = MESH_SEQ) -> dict:
    """``fpft`` and ``hift`` on the 1x4 mesh, then ``hift`` unsharded, all
    through ``launch.train`` with the same seed and batches.  Returns each
    run's losses and each device's peak bytes after the run (the peak is
    the process's running maximum)."""
    import jax

    from repro.launch import train as launch_train

    common = ["--arch", MESH_ARCH, "--optimizer", "adamw",
              "--policy", MESH_POLICY, "--steps", str(MESH_STEPS),
              "--batch", str(batch), "--seq", str(seq),
              *(["--smoke"] if smoke else [])]
    hift = ["--strategy", "hift", "--m", str(MESH_M), "--order", MESH_ORDER]
    runs = {"fpft_sharded": ["--strategy", "fpft", "--mesh", MESH_SPEC],
            "hift_sharded": hift + ["--mesh", MESH_SPEC],
            "hift_unsharded": hift}
    res = {}
    for name, args in runs.items():
        t0 = time.perf_counter()
        out = launch_train.main(common + args)
        jax.block_until_ready(out.pop("state"))
        res[name] = {"losses": out["losses"], "peaks": _peak_bytes(),
                     "wall_s": time.perf_counter() - t0}
        del out
        gc.collect()              # free this run's device arrays before the next
    return res


def check_mesh(res: dict, *, sharded_rtol: float = HIFT_SHARDED_RTOL,
               step1_rtol: float = FPFT_STEP1_RTOL) -> None:
    """Sharded hift tracks unsharded hift; fpft's step-1 loss (same params,
    same batch, no update yet) equals hift's."""
    for run in res.values():
        _require(all(math.isfinite(x) for x in run["losses"]),
                 f"losses {run['losses']}")
    hs, hu = res["hift_sharded"]["losses"], res["hift_unsharded"]["losses"]
    _require(len(hs) == len(hu), f"{len(hs)} vs {len(hu)} steps")
    for a, b in zip(hs, hu):
        _require(abs(a - b) <= sharded_rtol * abs(b),
                 f"sharded hift {hs} vs unsharded {hu} (rtol {sharded_rtol})")
    f1, h1 = res["fpft_sharded"]["losses"][0], hu[0]
    _require(abs(f1 - h1) <= step1_rtol * abs(h1),
             f"fpft step-1 loss {f1} vs hift {h1} (rtol {step1_rtol})")


# -------------------------------------------------------------------- main

def run_one_chip() -> None:
    t0 = time.perf_counter()
    run = train_phase()
    wall = time.perf_counter() - t0
    check_train(run)
    k, dts = run["k"], run["step_times"]
    print(f"train: {run['cfg'].name}, hift k={k} m={run['m']}, batch "
          f"{run['batch']} x seq {run['seq']}, losses {run['losses']}",
          flush=True)
    print(f"train: fused AdamW compiled (interpret=False) in "
          f"{len(run['fused_interpret'])} traced launches; all {k} inactive "
          f"bundles in pinned_host", flush=True)
    print(f"train (informational): launcher wall {wall} s, first visits "
          f"(compile + step) {sum(dts[:k])} s, revisit step of group 0 "
          f"{dts[k]} s, peak_bytes_in_use {_peak_bytes()}", flush=True)

    t0 = time.perf_counter()
    res = serve_phase(run["cfg"], run["state"])
    check_serve(res)
    same, total = agreement(res)
    print(f"serve: {len(res['outs'])} requests, prompt lengths "
          f"{list(SERVE_PROMPT_LENS)}, {res['max_new']} new tokens each, "
          f"{res['decode_steps']} decode steps, cache occupancy "
          f"{res['occupancy']} after the run", flush=True)
    print(f"serve (informational): {same}/{total} tokens equal the "
          f"ServeEngine greedy reference; wall {time.perf_counter() - t0} s, "
          f"peak_bytes_in_use {_peak_bytes()}", flush=True)


def run_four_chips() -> None:
    res = mesh_phase()
    for name, run in res.items():
        print(f"mesh: {name} losses {run['losses']} wall {run['wall_s']} s, "
              f"peak_bytes_in_use per device so far {run['peaks']}",
              flush=True)
    check_mesh(res)
    print(f"mesh: sharded hift within rtol {HIFT_SHARDED_RTOL} of unsharded; "
          f"fpft step-1 loss within rtol {FPFT_STEP1_RTOL} of hift's",
          flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve on one chip; 4: only the "
                         "1x4 mesh phase")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache

    device = device_phase(args.chips)
    print(f"compile cache: {setup_compile_cache()}", flush=True)
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
