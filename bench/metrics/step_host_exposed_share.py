"""Share of the traced window in which the device is idle inside the
program's step (``repro.train_step``) but outside its bundle transfers:
the grouped step's other host work (split, placing the inputs, dispatch,
write-back), over the window."""
from bench.lib import program
from bench.lib import trace


def read(facts):
    tr = program.extended(facts)
    if tr is None:
        return None
    return 100.0 * program.step_host_idle_ns(tr) / trace.window_ns(tr)
