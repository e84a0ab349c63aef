"""The training state the program keeps in device memory between steps
(parameters, and optimizer state that is not offloaded), read from the
runner's arrays after the window: the floor under the peak."""


def read(facts):
    b = facts.get("resident_bytes")
    return b / 2 ** 30 if b else None
