"""The manifest lint, and the lint's own teeth."""

import copy

from benchmark import manifest as mf


def test_the_manifest_is_clean():
    assert mf.lint(mf.load_manifest()) == []


def test_every_reader_declares_what_the_manifest_says():
    for m in mf.load_manifest()["per_layer"]:
        reader = mf.load_layer_metric(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]


def test_configuration_file_and_its_program_settings_agree():
    m = mf.load_manifest()
    for c in m["configs"]:
        cfg = mf.load_json(f"{mf.ROOT}/{c['file']}")
        env = cfg["program"]["env"]
        assert env["DCT_D_MODEL"] == cfg["hidden_size"]
        assert env["DCT_D_FF"] == cfg["intermediate_size"]
        assert env["DCT_N_HEADS"] == cfg["num_attention_heads"]
        assert env["DCT_N_KV_HEADS"] == cfg["num_key_value_heads"]
        assert env["DCT_N_LAYERS"] == cfg["num_hidden_layers"]
        assert env["DCT_ATTN_WINDOW"] == cfg["sliding_window"]
        # `reduced` is the cuts of scale, each with its published value and
        # its reason; no width among them. What else departs from the
        # published model is prose under `departures`.
        assert set(cfg["published"]) == set(c["reduced"]) == set(
            cfg["reduced_why"])
        for key, published in cfg["published"].items():
            assert cfg[key] != published, key
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size", "_heads"))
        assert cfg["departures"]


def test_the_lint_bites():
    good = mf.load_manifest()

    def broken(edit):
        m = copy.deepcopy(good)
        edit(m)
        return mf.lint(m)

    assert broken(lambda m: m["workloads"][0].update(name="a b"))
    assert broken(lambda m: m["end_to_end"][0].update(unit="tokens per s"))
    assert broken(lambda m: m["end_to_end"][0].update(bound=0.2))
    assert broken(lambda m: m["workloads"][0].update(chips=4))  # 2 of 3
    assert broken(lambda m: m["workloads"][0].update(traffic="nope"))
    assert broken(lambda m: m["per_layer"][0].update(moves="nope"))
    assert broken(lambda m: m["per_layer"][0].update(name="no.such.reader"))
    assert broken(lambda m: m["per_layer"][0].update(why="x"))
    assert broken(lambda m: m.update(run_seconds=52))
    assert broken(lambda m: m["end_to_end"].pop())  # setup_s gone
