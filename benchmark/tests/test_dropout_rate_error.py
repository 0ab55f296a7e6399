"""What PR 34 added: the reader of ``step.dropout_rate_error`` against a
few hand-made events, and its entry in ``BENCHMARK.json``."""

import pytest

from benchmark import manifest as mf

NAME = "step.dropout_rate_error"
CONFIG = {"program": {"env": {"DCT_DROPOUT": 0.1}}}


def read(events):
    return mf.load_layer_metric(NAME).read(
        {"events": events, "config": CONFIG})


def epoch_end(epoch, **counted):
    return {"event": "epoch_end", "epoch": epoch, "val_loss": 0.5, **counted}


def test_the_window_epochs_are_read_and_the_warm_up_epoch_left_out():
    events = [
        {"event": "run_start"},
        epoch_end(0, dropout_kept=500.0, dropout_total=1000.0),  # set-up
        {"event": "compile.window", "dropout_total": 7.0},
        epoch_end(1, dropout_kept=8990.0, dropout_total=10000.0),
        epoch_end(2, dropout_kept=9020.0, dropout_total=10000.0),
    ]
    assert read(events) == pytest.approx(abs(1 - 18010 / 20000 - 0.1))
    assert read(events) == pytest.approx(5e-4)


def test_a_window_of_one_epoch_leaves_the_warm_up_epochs_event():
    # The closing stamp ends fit before the window's one epoch is written.
    assert read([epoch_end(0, dropout_kept=905.0, dropout_total=1000.0)]) \
        == pytest.approx(5e-3)


@pytest.mark.parametrize("events", [
    [],
    [epoch_end(0), epoch_end(1)],  # dropout 0, or the parent's program
    [epoch_end(0, moe_rows=9000.0)],
], ids=["no_events", "no_counters", "other_counters"])
def test_none_where_no_epoch_counted_a_mask(events):
    assert read(events) is None


def test_the_entry_says_what_the_reader_says():
    reader = mf.load_layer_metric(NAME)
    manifest = mf.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "lower",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES}
    # The cells whose configuration drops anything at all.
    assert cells and len(set(cells)) == len(cells)
    for name in cells:
        _cell, config, _traffic = mf.load_cell(manifest, name)
        assert config["program"]["env"]["DCT_DROPOUT"] > 0, name
