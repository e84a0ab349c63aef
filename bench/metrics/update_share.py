"""Share of the device's busy time in the traced window spent in the
optimizer update: instants whose innermost operation is under the step's
``update`` name scope or is a fused update kernel."""
from bench.lib import program


def read(facts):
    split = program.device_split(facts)
    return None if split is None else 100.0 * split["update"] / split["busy"]
