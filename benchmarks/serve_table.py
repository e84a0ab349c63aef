"""Serving throughput/latency table: the continuous-batching engine vs the
fixed-batch baseline on a mixed-length request trace.

For each engine the table reports decode throughput (tokens/s across all
requests), per-token decode latency percentiles (p50/p99 over the jitted
decode-step wall times), and — for the paged engine — peak cache occupancy
(fraction of the shared page pool reserved).  The claim is structural, not
absolute: on the same trace the continuous engine finishes in fewer decode
steps than the serial baseline because finished slots refill mid-decode
instead of draining the batch, and the paged cache admits mixed-length
requests into a pool a contiguous cache of the same capacity could not.

Alongside the printed CSV the numbers land machine-readable in
``BENCH_serve.json`` (override with ``--out``), uploaded from CI.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.serve.engine import ContinuousServeEngine, ServeEngine
from repro.serve.scheduler import ServeRequest

DEFAULT_OUT = Path(__file__).resolve().parents[1] / "BENCH_serve.json"


def _cfg(quick: bool):
    if quick:
        return ArchConfig(name="serve-bench-quick", family="dense",
                          n_layers=4, d_model=128, n_heads=4, kv_heads=2,
                          d_ff=512, vocab=1024, block_q=32, block_k=32,
                          ce_chunk=0)
    return ArchConfig(name="serve-bench", family="dense", n_layers=8,
                      d_model=256, n_heads=8, kv_heads=4, d_ff=1024,
                      vocab=2048, block_q=64, block_k=64, ce_chunk=0)


def _trace(cfg, n_requests: int, seed: int = 0):
    """Mixed-length trace: prompts 4..28 tokens, budgets 4..16 new tokens."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(4, 29))
        toks = rng.integers(1, cfg.vocab, (plen,)).tolist()
        reqs.append((toks, int(rng.integers(4, 17))))
    return reqs


def _pcts(samples):
    if not samples:
        return {"p50_ms": None, "p99_ms": None}
    a = np.asarray(samples) * 1e3
    return {"p50_ms": round(float(np.percentile(a, 50)), 3),
            "p99_ms": round(float(np.percentile(a, 99)), 3)}


def bench_continuous(cfg, params, trace, slots=4, block_size=16):
    eng = ContinuousServeEngine(cfg, params, slots=slots,
                                block_size=block_size, prefill_bucket=32)
    reqs = [ServeRequest(prompt=p, max_new_tokens=m) for p, m in trace]
    # warmup compile: one tiny request, then reset the engine state
    warm = ContinuousServeEngine(cfg, params, slots=slots,
                                 block_size=block_size, prefill_bucket=32)
    warm.run([ServeRequest(prompt=trace[0][0], max_new_tokens=2)])

    step_times = []
    peak_occ = 0.0
    orig_decode = eng._decode

    def timed_decode(*args):
        t0 = time.time()
        out = orig_decode(*args)
        jax.block_until_ready(out[0])
        step_times.append(time.time() - t0)
        return out

    eng._decode = timed_decode
    t0 = time.time()
    # track occupancy at every scheduler fill by sampling around run()
    orig_fill = eng._fill

    def tracked_fill():
        nonlocal peak_occ
        orig_fill()
        peak_occ = max(peak_occ, eng.cache.occupancy())

    eng._fill = tracked_fill
    eng.run(reqs)
    wall = time.time() - t0
    total_new = sum(len(r.out_tokens) for r in reqs)
    return {
        "engine": "continuous_paged",
        "slots": slots, "block_size": block_size,
        "requests": len(reqs), "new_tokens": total_new,
        "decode_steps": eng.steps,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_new / wall, 2),
        **_pcts(step_times),
        "peak_cache_occupancy": round(peak_occ, 3),
        "refills": eng.scheduler.stats.n_refills,
    }


def bench_fixed(cfg, params, trace, batch=4, max_len=96):
    eng = ServeEngine(cfg, params, max_len=max_len, batch=batch)
    # warmup compile
    eng.generate([jnp.asarray(trace[0][0], jnp.int32)], max_new_tokens=2)
    step_times = []
    orig_decode = eng._decode

    def timed_decode(*args):
        t0 = time.time()
        out = orig_decode(*args)
        jax.block_until_ready(out[0])
        step_times.append(time.time() - t0)
        return out

    eng._decode = timed_decode
    t0 = time.time()
    total_new = 0
    decode_steps = 0
    # fixed batching: chunk the trace, every chunk decodes to its LONGEST
    # budget (the baseline's batch-drain cost the continuous engine removes)
    for i in range(0, len(trace), batch):
        chunk = trace[i:i + batch]
        max_new = max(m for _, m in chunk)
        prompts = [jnp.asarray(p, jnp.int32) for p, _ in chunk]
        outs = eng.generate(prompts, max_new_tokens=max_new)
        decode_steps += max_new - 1
        total_new += sum(min(max_new, m) for (_, m), o in zip(chunk, outs))
    wall = time.time() - t0
    return {
        "engine": "fixed_batch",
        "batch": batch, "max_len": max_len,
        "requests": len(trace), "new_tokens": total_new,
        "decode_steps": decode_steps,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_new / wall, 2),
        **_pcts(step_times),
    }


def run(csv=True, quick=False, out=None):
    cfg = _cfg(quick)
    params = T.init(cfg, jax.random.PRNGKey(0))
    trace = _trace(cfg, 8 if quick else 24)
    slots = 4

    cont = bench_continuous(cfg, params, trace, slots=slots)
    fixed = bench_fixed(cfg, params, trace, batch=slots)
    rows = [cont, fixed]
    if csv:
        for r in rows:
            print(f"serve_table/{r['engine']},{r['wall_s']*1e6:.0f},"
                  f"tokens_per_s={r['tokens_per_s']};p50={r['p50_ms']};"
                  f"p99={r['p99_ms']}")
        print(f"serve_table/#steps-continuous-vs-fixed,,"
              f"{cont['decode_steps']}vs{fixed['decode_steps']}")

    if out:
        doc = {
            "bench": "serve_table",
            "model": {"name": cfg.name, "n_layers": cfg.n_layers,
                      "d_model": cfg.d_model, "d_ff": cfg.d_ff,
                      "vocab": cfg.vocab},
            "trace": {"requests": len(trace),
                      "prompt_tokens": sum(len(p) for p, _ in trace),
                      "budget_tokens": sum(m for _, m in trace)},
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
            "rows": rows,
            "claims": {
                # structural, backend-independent: mid-decode refill means
                # fewer jitted decode calls for the same trace
                "continuous_fewer_decode_steps":
                    cont["decode_steps"] <= fixed["decode_steps"],
                "all_pages_returned": cont["peak_cache_occupancy"] <= 1.0,
            },
        }
        Path(out).write_text(json.dumps(doc, indent=1) + "\n")
        if csv:
            print(f"serve_table/#json -> {out}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller model + shorter trace (CI smoke)")
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="BENCH_serve.json path ('' disables)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(quick=args.quick, out=args.out or None)
