"""The reader of ``moe.tiled_over_routed`` against hand-made epochs'
counters, the parent's counters, and its entry in ``BENCHMARK.json``."""

import types

import pytest

from benchmark import manifest as mf

NAME = "moe.tiled_over_routed"
ROUTED_CELLS = [
    "lfm2_24b_ep8.fit_seq8192_balanced", "moonlight_16b_ep8.fit_seq8192",
    "mellum2_12b.fit_seq8192", "laguna_xs2_ep16.fit_seq8192"]


def read(counters):
    return mf.load_layer_metric(NAME).read(
        {"window": types.SimpleNamespace(**counters)})


def test_the_tiled_rows_are_read_as_their_ratio_to_the_routed_rows():
    warm_up = {"moe_rows": 1.0, "moe_rows_tiled": 99.0}
    assert read({"counters": [warm_up, {
        "moe_rows": 9000.0, "moe_rows_tiled": 9984.0,
        "moe_rows_bound": 10240.0}, {
        "moe_rows": 7000.0, "moe_rows_tiled": 7424.0}]}) == pytest.approx(
            (9984 + 7424) / 16000)
    # Every group on a tile boundary: no padding.
    assert read({"counters": [warm_up, {
        "moe_rows": 4096.0, "moe_rows_tiled": 4096.0}]}) == 1.0


def test_the_parents_counters_give_nothing():
    """The parent's program counts no tiled rows (XLA's ragged_dot), and
    the cells without routed experts count no rows at all."""
    warm_up = {"moe_rows": 1.0, "moe_rows_bound": 5120.0}
    assert read({"counters": [warm_up, {
        "moe_rows": 9000.0, "moe_rows_bound": 10240.0,
        "moe_rows_overflowed": 0.0}]}) is None
    assert read({"counters": [{}, {"dropout_kept": 3.0}]}) is None
    assert read({}) is None


def test_the_entry_says_what_the_reader_says():
    reader = mf.load_layer_metric(NAME)
    manifest = mf.load_manifest()
    assert mf.lint(manifest) == []
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert manifest["per_layer"][-1] is entry
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "lower",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES}
    assert cells == ROUTED_CELLS
    for name in cells:
        _cell, _config, traffic = mf.load_cell(manifest, name)
        assert traffic["driver"] in ("fit_routed", "fit_routed_softmax")
