"""``bundle_in_place_share`` on hand-written span lists: the share of the
window's ``repro.bundle_offload`` spans whose ``in_place`` metadata is 1."""
import json
from pathlib import Path

import pytest

from bench.lib import spec

DATA = Path(__file__).parent / "data"


def read(name, tr):
    return spec.metric_reader(name)({"trace": tr})


@pytest.fixture
def offloads():
    return json.loads((DATA / "offload_spans_small.json").read_text())


@pytest.mark.parametrize("spans,share", [
    ("all_in_place", 100.0), ("half_in_place", 50.0), ("no_offload", None),
    ("unrecorded", None)])
def test_bundle_in_place_share_counts_the_windows_offloads(offloads, spans,
                                                           share):
    """Offload spans that start in the window, by their ``in_place``
    metadata; no offload, or a program that records no ``in_place`` (the
    one before it did), reads None."""
    tr = {"window": offloads["window"], "program": offloads[spans]}
    got = read("bundle_in_place_share", tr)
    assert got == (None if share is None else pytest.approx(share))


def test_bundle_in_place_share_is_declared_for_the_hift_cell():
    entry = {m["name"]: m for m in spec.benchmark()["per_layer"]}[
        "bundle_in_place_share"]
    assert entry["workloads"] == ["internlm2-l16-hift-b8s512"]
    assert (entry["unit"], entry["source"], entry["moves"]) == \
        ("%", "device_trace", "setup_s")
    assert entry["layer"] == "bundle transfer (core/pipeline.py)"
    assert read("bundle_in_place_share", None) is None
