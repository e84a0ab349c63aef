"""One run of a training cell: set-up, the measured window, the check.

Set-up builds the program's runner (``repro.core.registry.make_runner``,
the path ``repro.launch.train`` takes) on seeded weights and drives it
through the cell's first steps with the window's own call and feed: two
whole HiFT sweeps, in which each group's first visit compiles (or loads
from the cache) and creates its optimizer bundle and its revisit fetches
that bundle back from host memory, or FPFT's first steps.  What the check
needs from those steps is read before the window starts.
The window holds whole sweeps; then the peak memory is read, the
program's state is freed, and the plain reference follows the same first
steps from the seed.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import flops as FL
from bench.lib import model as M
from bench.lib import reference as REF
from bench.lib import spec as SPEC
from bench.lib import trace as TR
from bench.lib.peaks import peaks
from bench.lib.traffic import MarkovBatches

OUT_DIR = SPEC.BENCH_DIR / ".out"
# a traced run's window: whole sweeps until this many seconds have passed
# (one sweep where a sweep is longer), so that a trace stays some hundred
# thousand device events
TRACE_SECONDS = 3.0
# a compared number that cannot be read (a missing leaf, a loss that is
# not finite) reads as this
NO_READING = 1e30


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), so set-up
    counts the interpreter's start and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


def require_chips(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_
    CACHE_DIR``, else ``.jax_cache`` in the checkout), keeping every
    program however fast it compiled, so that only a cell's first run in a
    checkout compiles."""
    from repro.launch.compile_cache import setup_compile_cache
    where = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


class CompileCounter:
    """Counts the backend compiles JAX reports while ``on``."""

    def __init__(self):
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and "backend_compile" in event:
            self.count += 1


# ------------------------------------------------------------------ program

def build_runner(cfg, c: dict, w: dict, seed: int):
    from repro.core import HiFTConfig, LRSchedule, make_runner
    from repro.optim.mixed_precision import get_policy

    params = M.init_params(c, seed, REF.PARAM_DTYPE[w["policy"]])
    kw = {"schedule": LRSchedule(base_lr=float(w["lr"]), kind="constant"),
          "policy": get_policy(w["policy"])}
    if w["strategy"] == "hift":
        kw["hift"] = HiFTConfig(m=int(w["m"]), strategy=w["order"], seed=0)
    runner = make_runner(cfg, w["strategy"], params=params,
                         optimizer=w["optimizer"], **kw)
    del params
    return runner


def resident_bytes(state) -> int:
    """Bytes of the state's arrays that live in device memory."""
    return sum(x.nbytes for x in jax.tree.leaves(state)
               if isinstance(x, jax.Array)
               and x.sharding.memory_kind in (None, "device"))


def _feed(toks: np.ndarray) -> dict:
    t = jnp.asarray(toks)
    return {"tokens": t, "labels": t}


def _on_device(tree):
    """A copy in device memory of a tree that may live in pinned host."""
    if jax.devices()[0].platform == "cpu":
        return tree
    return jax.device_put(tree, jax.tree.map(
        lambda x: x.sharding.with_memory_kind("device"), tree))


def _grads(m_tree: dict, layer=None) -> tuple:
    """Norms and samples of the first gradients, from AdamW's first moment
    after one update: m = (1 - b1) g."""
    scale = 1.0 / (1.0 - REF.ADAMW["b1"])
    return ({k: v * scale for k, v in REF.named_norms(m_tree, layer).items()},
            {k: v * scale for k, v in REF.named_samples(m_tree,
                                                        layer).items()})


def _diff(a, b):
    return jax.tree.map(lambda x, y: x.astype(jnp.float32)
                        - y.astype(jnp.float32), a, b)


def _bundles(runner, c: dict):
    """``(key, layer, bundle)`` of each HiFT group that has a bundle: the
    parameters' top-level key, the block's index (``None`` for the
    embedding and the head) and its bundle, wherever the program keeps
    it."""
    n = c["num_hidden_layers"]
    for gi in range(n + 2):
        bundle = runner.state.opt_state.get(str(gi))
        if bundle is not None:
            yield (("embed", None) if gi == 0 else ("head", None)
                   if gi == n + 1 else ("layers", gi - 1)) + (bundle,)


def _part(tree: dict, key: str, layer):
    """The trained leaves under ``key``; a block's bundle holds them with
    a leading dimension of one."""
    sub = tree[key]
    return sub if layer is None else jax.tree.map(lambda x: x[0], sub)


def first_grads(runner, c: dict, w: dict) -> tuple:
    """Norms and samples of each trained leaf's first gradient, read from
    AdamW's first moment once every group has had one update."""
    if w["strategy"] != "hift":
        return _grads(runner.state.opt_state["m"])
    norms, samples = {}, {}
    for key, layer, bundle in _bundles(runner, c):
        m = _part(_on_device(bundle["opt"]["m"]), key, layer)
        n, s = _grads({key: m}, layer)
        norms.update(n)
        samples.update(s)
        del m
    return norms, samples


def last_readings(runner, c: dict, w: dict, seed: int) -> dict:
    """The norms of each trained leaf's change since the seed and, under
    HiFT, of its first moment: from each group's optimizer bundle (its
    float32 master under Mixed^Hi) after the revisits, or from FPFT's
    parameters."""
    p0 = M.init_params(c, seed, REF.PARAM_DTYPE[w["policy"]])
    params = runner.state.params
    if w["strategy"] != "hift":
        return {"change": REF.named_norms(_diff(params, p0))}
    out = {"change": {}, "moment": {}}
    for key, layer, bundle in _bundles(runner, c):
        m = _part(_on_device(bundle["opt"]["m"]), key, layer)
        if "master" in bundle:
            new = _part(_on_device(bundle["master"]), key, layer)
        else:
            new = jax.tree.map(lambda x: x if layer is None else x[layer],
                               params[key])
        start = jax.tree.map(lambda x: x if layer is None else x[layer],
                             p0[key])
        out["moment"].update(REF.named_norms({key: m}, layer))
        out["change"].update(REF.named_norms({key: _diff(new, start)},
                                             layer))
        del m, new
    return out


def compare(prog: dict, ref: dict) -> dict:
    """The compared numbers, each ``(value, where)``: the worst over steps
    or leaves, and which step or leaf that was.

    ``loss_gap``: largest relative gap of a step's loss.  ``grad_norm_gap``,
    ``change_norm_gap`` and, under HiFT, ``moment_norm_gap`` (first moment
    after the last visit): largest gap between the program's and the
    reference's norm of a leaf, over the larger of the reference's norm of
    that leaf and of the median leaf.  The change leaves out leaves whose
    reference gradient is under a thousandth of the median leaf's (they
    move under Adam by round-off alone).  ``grad_diff_gap``: largest norm
    of the difference of a leaf's sampled first gradients, over the larger
    of the reference sample's norm and the median leaf's; gaps of norms
    see a precision's rounding only in second order, this in first."""
    def worst(pairs):
        pairs = list(pairs)
        if not pairs or not all(math.isfinite(v) for v, _ in pairs):
            return NO_READING, None
        return max(pairs, key=lambda p: p[0])

    lp, lr = prog.get("loss", []), ref["loss"]
    loss = worst((abs(a - b) / abs(b), f"step {i}")
                 for i, (a, b) in enumerate(zip(lp, lr))) \
        if len(lp) == len(lr) else (NO_READING, "steps")

    def gap(p: dict, r: dict, keys) -> tuple:
        keys = list(keys)
        med = statistics.median(r[k] for k in keys)
        return worst((abs(p.get(k, 0.0) - r[k]) / max(r[k], med), k)
                     for k in keys)

    g_ref = ref["grad"]
    g_med = statistics.median(g_ref.values())
    moved = [k for k in ref["change"] if g_ref.get(k, 0.0) >= 1e-3 * g_med]
    s_ref, s_prog = ref["grad_sample"], prog.get("grad_sample", {})
    s_norm = {k: float(np.linalg.norm(v)) for k, v in s_ref.items()}
    s_med = statistics.median(s_norm.values())
    diff = worst(((float(np.linalg.norm(s_prog[k] - v))
                   if k in s_prog and s_prog[k].shape == v.shape
                   else s_norm[k]) / max(s_norm[k], s_med), k)
                 for k, v in s_ref.items())
    out = {"loss_gap": loss,
           "grad_diff_gap": diff,
           "grad_norm_gap": gap(prog.get("grad", {}), g_ref, g_ref),
           "change_norm_gap": gap(prog.get("change", {}), ref["change"],
                                  moved)}
    if "moment" in ref:
        out["moment_norm_gap"] = gap(prog.get("moment", {}), ref["moment"],
                                     ref["moment"])
    return out


# ------------------------------------------------------------------- a run

def run(cell: SPEC.Cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, plant=None, log=sys.stderr) -> dict:
    """One run of ``cell``; returns the result object.  ``plant(runner)``
    may break the runner before set-up drives it (the fault tests)."""
    def say(msg):
        print(msg, file=log, flush=True)

    devs = require_chips(cell.chips) if require_tpu else jax.devices()[:1]
    dev = devs[0]
    counter = CompileCounter()

    c, w, mix = cell.config, cell.workload, cell.traffic
    cfg = M.arch_config(c)
    gen = MarkovBatches(mix, c["vocab_size"], seed)
    sweep = REF.sweep_length(c, w) if w["strategy"] == "hift" else 1
    warm = REF.first_steps(c, w)
    check_s = 0.0

    runner = build_runner(cfg, c, w, seed)
    if plant is not None:
        plant(runner)
    prog = {"loss": []}
    took = []
    for step in range(warm):
        t = time.perf_counter()
        loss = float(runner.train_step(_feed(gen.batch_at(step))))
        took.append(time.perf_counter() - t)
        prog["loss"].append(loss)
        if step == sweep - 1:           # every group has had one update
            jax.block_until_ready(runner.state)
            t = time.perf_counter()
            prog["grad"], prog["grad_sample"] = first_grads(runner, c, w)
            check_s += time.perf_counter() - t
    jax.block_until_ready(runner.state)
    t = time.perf_counter()
    prog.update(last_readings(runner, c, w, seed))
    jax.block_until_ready(runner.state)
    check_s += time.perf_counter() - t
    setup_s = process_age_s() - check_s
    say(f"setup: {setup_s} s ({warm} steps; {check_s} s of check "
        "readings left out)")
    say("setup step seconds: " + " ".join(f"{x:.3f}" for x in took))

    # ---------------------------------------------------------- window
    trace_dir = OUT_DIR / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(trace_dir))
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    groups = FL.groups(c, int(w["m"])) if w["strategy"] == "hift" else [None]
    attempted = failed = 0
    window_groups, step_ends = [], []
    step = warm
    counter.on = True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        sweeps = 0
        while True:
            ok = True
            for _ in range(sweep):
                attempted += 1
                gi = step % sweep
                with jax.profiler.TraceAnnotation("bench.batch"):
                    feed = _feed(gen.batch_at(step))
                try:
                    label = f"bench.train_step g{gi}"
                    with jax.profiler.TraceAnnotation(label):
                        loss = runner.train_step(feed)
                    with jax.profiler.TraceAnnotation("bench.loss_readback"):
                        loss = float(loss)
                    ok = math.isfinite(loss)
                except Exception as e:   # a step that raises is a failed step
                    say(f"window step {step} raised: {e!r}")
                    ok = False
                failed += not ok
                step_ends.append(time.perf_counter() - t0)
                window_groups.append(groups[gi])
                step += 1
                if not ok:
                    break
            sweeps += 1
            elapsed = time.perf_counter() - t0
            if not ok or elapsed + elapsed / sweeps > seconds:
                break
        jax.block_until_ready(runner.state)
    window_s = time.perf_counter() - t0
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    tokens = len(window_groups) * gen.tokens_per_step
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"compiles in window: {counter.count}", flush=True)
    say(f"window: {window_s} s, {len(window_groups)} steps in {sweeps} "
        f"sweeps, {counter.count} compiles, peak_bytes_in_use {peak}")
    if sweep > 1:
        say("window step seconds: " + " ".join(
            f"{b - a:.3f}" for a, b in zip([0.0] + step_ends, step_ends)))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}
    facts = {"config": c, "workload": w, "traffic": mix,
             "window_groups": window_groups, "window_s": window_s,
             "device_kind": dev.device_kind, "trace": None,
             "resident_bytes": resident_bytes(runner.state)}
    if trace:
        tr = TR.load(str(trace_dir))
        facts["trace"] = tr
        device["busy_s"] = TR.busy_ns(tr) / 1e9
        device["window_s"] = TR.window_ns(tr) / 1e9
        result["breakdown"] = {"device_ops": TR.top_ops(tr),
                               "idle_gaps": TR.idle_gaps(tr)}
        for m in cell.per_layer:
            value = SPEC.metric_reader(m["name"])(facts)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        e2e = {"train_tokens_per_s": tokens / window_s,
               "peak_hbm_gib": (peak or 0) / 2 ** 30, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    # ----------------------------------------------------------- check
    del runner
    gc.collect()
    t = time.perf_counter()
    ref = REF.replay(c, w, mix, seed)
    gaps = compare(prog, ref)
    say(f"reference: {time.perf_counter() - t} s for {warm} steps")
    limits = w["limits"]
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, (v, _) in gaps.items()}
    within = all(ch["limit"] is not None and ch["value"] <= ch["limit"]
                 for ch in checks.values())
    result["correct"] = bool(within and failed == 0 and attempted > 0)
    result["checks"] = checks
    for k, ch in checks.items():
        say(f"check {k}: {ch['value']} limit {ch['limit']} "
            f"(worst: {gaps[k][1]})")
    say(f"run: {process_age_s()} s since the process started")
    return result
