"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
        --steps 20 --strategy hift --m 2 --order bottom2up --optimizer adamw

Selects any assigned architecture (--arch) and any registered fine-tuning
strategy (--strategy hift|fpft|fpft_streamed|mezo|lisa|lomo|adalomo|...,
resolved via
``repro.core.registry``), wires the deterministic data pipeline,
checkpointing and the straggler watchdog.  On a real TPU cluster this same
entry point runs per-host under the (data, model) mesh; ``--mesh DxM``
(e.g. ``--mesh 2x4``) compiles the strategy step with the dist.shardings
placement rules.  On a CPU-only host, fabricate devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \\
        --smoke --steps 8 --strategy hift --mesh 2x4

(``./run.sh -m repro.launch.train ...`` exports the flag for you; see
docs/sharding.md.)
"""
from __future__ import annotations

import argparse
import functools

import jax

from repro.configs.registry import ARCH_IDS, PAPER_IDS, get_config
from repro.core import (AdaLomoConfig, HiFTConfig, LiSAConfig, LOMOConfig,
                        LRSchedule, MeZOConfig, make_runner, registry)
from repro.data.synthetic import DataConfig, PrefetchIterator, SyntheticLM
from repro.launch.compile_cache import setup_compile_cache
from repro.models import get_family
from repro.optim.mixed_precision import get_policy
from repro.train.loop import LoopConfig, train


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help=f"one of {ARCH_IDS + PAPER_IDS}")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    # resolved at parse time so late-registered strategies show up too
    ap.add_argument("--strategy", default="hift",
                    choices=registry.strategy_ids(),
                    help="fine-tuning strategy (registry-resolved)")
    ap.add_argument("--m", type=int, default=1,
                    help="units per group (hift/lisa)")
    ap.add_argument("--order", default="bottom2up",
                    choices=["bottom2up", "top2down", "random"],
                    help="HiFT group visit order")
    ap.add_argument("--switch-every", type=int, default=5,
                    help="LiSA re-sampling period")
    ap.add_argument("--grad-clip", type=float, default=None,
                    help="lomo/adalomo global-norm clip (0 disables the norm "
                         "sweep; default 1.0 for lomo, 0 for adalomo whose "
                         "per-matrix update-RMS clip already bounds steps)")
    ap.add_argument("--fused-update", dest="fused_update",
                    action="store_true", default=None,
                    help="force the fused Pallas optimizer update "
                         "(adamw/sgdm/adagrad); default auto: fused on TPU")
    ap.add_argument("--no-fused-update", dest="fused_update",
                    action="store_false",
                    help="force the unfused elementwise update")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    help=">=2 pipelines hift/lisa optimizer-bundle "
                         "host<->device transfers with depth-1 lookahead "
                         "(core.pipeline); hift_pipelined defaults to 2; "
                         "for fpft_streamed it sets the chunk window depth")
    ap.add_argument("--stream-window", type=int, default=None,
                    help="fpft_streamed chunk size in bytes "
                         "(StreamConfig.chunk_bytes); the device-resident "
                         "optimizer window is pipeline-depth chunks")
    ap.add_argument("--mesh", default=None,
                    help="device mesh for sharded steps: DxM (data x model, "
                         "e.g. 2x4) or name=size pairs (data=2,model=4); "
                         "under --coordinator the mesh spans the GLOBAL "
                         "device list of all coordinated processes")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 — joins a jax.distributed "
                         "multi-process job (every process runs this same "
                         "command with its own --process-id)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total process count of the multi-process job")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank in [0, num_processes)")
    ap.add_argument("--local-devices", type=int, default=None,
                    help="fabricate N host CPU devices per process "
                         "(multi-host testing without accelerators)")
    ap.add_argument("--crosspod-pods", type=int, default=0,
                    help=">=2 splits each batch into that many pod chunks "
                         "and reduces per-pod gradients (fpft/hift/lisa)")
    ap.add_argument("--crosspod-exact", action="store_true",
                    help="cross-pod reduce WITHOUT int8 EF compression "
                         "(default compresses the wire)")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--policy", default="fp32",
                    choices=["fp32", "mixed", "mixed_hi", "bf16"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--fpft", action="store_true",
                    help="deprecated alias for --strategy fpft")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()

    if args.coordinator:
        if args.num_processes is None or args.process_id is None:
            ap.error("--coordinator requires --num-processes and "
                     "--process-id")
        from repro.launch.mesh import init_distributed
        init_distributed(args.coordinator, args.num_processes,
                         args.process_id,
                         local_device_count=args.local_devices)
        print(f"distributed: process {jax.process_index()}/"
              f"{jax.process_count()}, {len(jax.devices())} global devices")

    cfg = get_config(args.arch, smoke=args.smoke)
    fam = get_family(cfg)
    init = functools.partial(fam.init, cfg)
    mesh = None
    if args.mesh:
        from repro.dist.shardings import param_shardings
        from repro.launch.mesh import mesh_from_spec
        mesh = mesh_from_spec(args.mesh)
        print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} over "
              f"{mesh.size}/{len(jax.devices())} "
              f"{jax.devices()[0].platform} devices")
        # born sharded: a whole fp32 tree on the first device would not fit
        # it beside that device's share of the run
        shapes = jax.eval_shape(init, jax.random.PRNGKey(args.seed))
        init = jax.jit(init, out_shardings=param_shardings(shapes, mesh))
    params = init(jax.random.PRNGKey(args.seed))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[{cfg.name}] {n/1e6:.1f}M params, family={cfg.family}")

    strategy = "fpft" if args.fpft else args.strategy
    sched = LRSchedule(base_lr=args.lr, kind="cosine",
                       total_cycles=max(args.steps, 1))
    kw = {"schedule": sched, "policy": get_policy(args.policy), "mesh": mesh,
          "fused_update": args.fused_update,
          "pipeline_depth": args.pipeline_depth}
    if args.stream_window is not None:
        kw["stream_window"] = args.stream_window
    if args.crosspod_pods and args.crosspod_pods >= 2:
        from repro.core import CrossPodConfig
        kw["cross_pod"] = CrossPodConfig(pods=args.crosspod_pods,
                                         compress=not args.crosspod_exact)
    if strategy in ("hift", "hift_pipelined"):
        kw["hift"] = HiFTConfig(m=args.m, strategy=args.order, seed=args.seed)
    elif strategy == "lisa":
        kw["lisa"] = LiSAConfig(m=args.m, switch_every=args.switch_every,
                                seed=args.seed)
    elif strategy == "mezo":
        kw["mezo"] = MeZOConfig(seed=args.seed)
    elif strategy == "lomo":
        kw["lomo"] = LOMOConfig(
            grad_clip=1.0 if args.grad_clip is None else args.grad_clip)
    elif strategy == "adalomo":
        kw["adalomo"] = AdaLomoConfig(
            grad_clip=0.0 if args.grad_clip is None else args.grad_clip)
    runner = make_runner(cfg, strategy, params=params,
                         optimizer=args.optimizer, seed=args.seed, **kw)
    # the runner holds its own (policy-cast, placed) copy; a cast or placed
    # copy leaves this tree as a second full-size one on device 0
    del params
    if strategy in ("hift", "hift_pipelined", "lisa"):
        print(f"{strategy} k={runner.k}, "
              f"peak trainable {runner.peak_trainable_params()/1e6:.2f}M "
              f"({100*runner.peak_trainable_params()/n:.2f}%)")

    if cfg.family in ("encdec", "vlm"):
        # frontend stubs: wrap the synthetic stream with the extra inputs
        import jax.numpy as jnp
        base = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                      global_batch=args.batch, seed=args.seed))

        class Wrapped:
            def __init__(self):
                self.s = 0
            def __next__(self):
                b = base.batch_at(self.s)
                self.s += 1
                k = jax.random.PRNGKey(self.s)
                if cfg.family == "encdec":
                    b["src_embeds"] = jax.random.normal(
                        k, (args.batch, args.seq, cfg.d_model))
                else:
                    b["vision_embeds"] = jax.random.normal(
                        k, (args.batch, cfg.vision_tokens, cfg.d_model))
                return b

        data = Wrapped()
    else:
        data = PrefetchIterator(SyntheticLM(DataConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
            seed=args.seed)))

    out = train(runner, data, LoopConfig(
        total_steps=args.steps, ckpt_every=max(args.steps // 2, 1),
        ckpt_dir=args.ckpt_dir, log_every=max(args.steps // 10, 1),
        resume=args.resume))
    print(f"done: final loss {out['losses'][-1]:.4f}")
    out["state"] = runner.state
    return out


if __name__ == "__main__":
    main()
