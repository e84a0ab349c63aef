"""Serving launcher: batched greedy generation for any --arch.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --requests 4 --max-new 16

``--continuous`` switches the dense families onto the continuous-batching
engine (paged KV cache + slot-level scheduler); ``--mesh 2x2`` serves
sharded on the same mesh spec grammar the trainer uses.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.compile_cache import setup_compile_cache
from repro.models import get_family
from repro.serve.engine import ContinuousServeEngine, ServeEngine
from repro.serve.scheduler import ServeRequest


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV cache "
                         "(dense families)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch width for --continuous")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged-cache page size for --continuous")
    ap.add_argument("--mesh", default=None,
                    help="mesh spec (e.g. 2x2) to serve sharded; same "
                         "grammar as the training launcher")
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    fam = get_family(cfg)
    params = fam.init(cfg, jax.random.PRNGKey(0))
    mesh = None
    if args.mesh:
        from repro.launch.mesh import mesh_from_spec
        mesh = mesh_from_spec(args.mesh)
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (16,), 0, cfg.vocab)
               for i in range(args.requests)]

    if args.continuous:
        engine = ContinuousServeEngine(cfg, params, slots=args.slots,
                                       block_size=args.block_size, mesh=mesh)
        reqs = [ServeRequest(prompt=list(map(int, p)),
                             max_new_tokens=args.max_new) for p in prompts]
        engine.run(reqs)
        outs = [r.out_tokens for r in reqs]
        stats = engine.scheduler.stats
        for i, o in enumerate(outs):
            print(f"request {i}: {o}")
        print(f"served {len(outs)} requests | decode steps {engine.steps} | "
              f"refills {stats.n_refills} | peak active {stats.peak_active}")
        return outs

    engine = ServeEngine(cfg, params, max_len=args.max_len,
                         batch=args.requests, mesh=mesh)
    kw = {}
    if cfg.family == "encdec":
        kw["src_embeds"] = jax.random.normal(
            jax.random.PRNGKey(99), (args.requests, args.max_len, cfg.d_model))
    outs = engine.generate(prompts, max_new_tokens=args.max_new, **kw)
    for i, o in enumerate(outs):
        print(f"request {i}: {o}")
    print(f"served {len(outs)} requests x {args.max_new} tokens")
    return outs


if __name__ == "__main__":
    main()
