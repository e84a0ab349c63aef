"""Share of the traced window's bundle offloads (``repro.bundle_offload``
spans that start in the window) that wrote the new bundle into its group's
existing pinned-host buffers: the span's metadata ``in_place`` is 1.
``None`` where no offload span of the window carries that metadata (a
program that does not record it, or a window with no offload)."""
from bench.lib import program

OFFLOAD = "repro.bundle_offload"


def read(facts):
    tr = program.extended(facts)
    if tr is None:
        return None
    lo, hi = tr["window"]
    flags = [stats["in_place"] for name, start, _, stats in tr["program"]
             if name == OFFLOAD and lo <= start < hi and "in_place" in stats]
    if not flags:
        return None
    return 100.0 * sum(1 for f in flags if f) / len(flags)
