"""Share of the traced window in which the device is idle while the
program moves an optimizer bundle between pinned host and device: idle
time inside the union of its ``repro.bundle_fetch``,
``repro.bundle_offload`` and ``repro.bundle_prefetch`` spans, over the
window."""
from bench.lib import program
from bench.lib import trace


def read(facts):
    tr = program.extended(facts)
    if tr is None:
        return None
    return 100.0 * program.bundle_idle_ns(tr) / trace.window_ns(tr)
