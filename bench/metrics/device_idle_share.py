"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window."""
from bench.lib import trace


def read(facts):
    tr = facts["trace"]
    if tr is None or not trace.ops(tr):
        return None
    return 100.0 * trace.idle_share(tr)
