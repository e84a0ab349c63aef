"""Benchmark entry point: one module per paper table/figure.

All training benchmarks build their drivers through the unified strategy
registry (``repro.core.registry.make_runner``), so every row is produced by
the same TrainState-in/TrainState-out step surface.

Prints ``name,us_per_call,derived`` CSV rows.

  memory_table         -> paper Tables 8-12 + Appendix-B equations
  trainable_params     -> paper Fig. 6(e)
  strategy_equivalence -> paper Fig. 4 (order + grouping ablations)
  convergence          -> paper Fig. 3 (loss stability)
  roofline             -> §Roofline report from the dry-run artifacts
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    print("name,us_per_call,derived")
    from benchmarks import (convergence, memory_table, roofline,
                            strategy_equivalence, trainable_params)
    ok = True
    for mod in [memory_table, trainable_params, strategy_equivalence,
                convergence, roofline]:
        try:
            mod.run(csv=True)
        except Exception as e:
            ok = False
            print(f"{mod.__name__}/ERROR,0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    try:
        from benchmarks.memory_table import check_paper_claims
        check_paper_claims()
    except Exception as e:
        ok = False
        print(f"paper_claims/ERROR,0,{e}")
    if not ok:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
