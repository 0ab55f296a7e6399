"""What PR 28 added: the reader of ``moe.bound_over_routed`` against a few
hand-made events, and its entry in ``BENCHMARK.json``."""

import types

import pytest

from benchmark import manifest as mf

NAME = "moe.bound_over_routed"


def read(counters):
    return mf.load_layer_metric(NAME).read(
        {"window": types.SimpleNamespace(**counters)})


def test_the_bound_is_read_as_its_ratio_to_the_routed_rows():
    warm_up = {"moe_rows": 1.0, "moe_rows_bound": 9.0}
    assert read({"counters": [warm_up, {
        "moe_rows": 9000.0, "moe_rows_bound": 16384.0}, {
        "moe_rows": 7000.0, "moe_rows_bound": 32768.0}]}) == pytest.approx(
            49152 / 16000)
    # The parent's program counts no bound; the other cells count nothing.
    assert read({"counters": [warm_up, {"moe_rows": 9000.0}]}) is None
    assert read({}) is None


def test_the_entry_says_what_the_reader_says():
    reader = mf.load_layer_metric(NAME)
    manifest = mf.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "lower",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES}
    # Which cells is a later PR's to extend: each has to exist and to run
    # the driver that keeps the counters.
    assert cells and len(set(cells)) == len(cells)
    for name in cells:
        _cell, _config, traffic = mf.load_cell(manifest, name)
        assert traffic["driver"] == "fit_routed", name
