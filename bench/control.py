#!/usr/bin/env python3
"""Readings that set the upper end of each compared number's limit.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed, on the chip at the cell's own size: the plain reference
(float32, ``highest``), then in the program's place the control (the
reference one precision lower than the cell's matmuls) and each fault the
cell can have: half of each batch left out and, in a HiFT cell that
revisits its groups, a revisit that starts from a fresh bundle or leaves
its group unchanged.  Prints one JSON line per seed and reading with the
compared numbers.  A state left unchanged from the first step reads 1 on
the gradient and change norms by construction and needs no run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench.lib import cell as C
    from bench.lib import reference as R
    from bench.lib import spec

    cell = spec.cell(args.workload)
    try:
        C.require_chips(cell.chips)
    except C.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    C.use_compile_cache()
    w = cell.workload
    for seed in args.seeds:
        t = time.perf_counter()
        ref = R.replay(cell, seed)
        readings = [(R.CONTROL[w["matmuls"]], None), ("fp32", "half_batch")]
        if w["strategy"] == "hift" and \
                R.first_steps(cell) > R.sweep_length(cell):
            readings += [("fp32", "revisit_fresh"),
                         ("fp32", "revisit_unchanged")]
        for precision, fault in readings:
            got = R.replay(cell, seed, precision=precision, fault=fault)
            gaps = C.compare(got, ref)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "reading": fault or f"control_{precision}",
                              **{k: v for k, (v, _) in gaps.items()},
                              "worst": {k: at for k, (_, at) in gaps.items()}
                              }), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t} s", file=sys.stderr,
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
