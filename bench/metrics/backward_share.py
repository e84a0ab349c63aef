"""Share of the device's busy time in the traced window spent in the
backward pass: instants whose innermost operation has ``transpose(`` in
its name stack (recomputed forward operations inside the backward count
here too)."""
from bench.lib import program


def read(facts):
    split = program.device_split(facts)
    return None if split is None else 100.0 * split["backward"] / split["busy"]
