"""The readers of the step's device scopes and of the three flash kernels,
against a hand-made program text joined to the recorded v5e sample
(``benchmark/reduce/sample/README.txt`` lists its events), with values
worked out by hand; and where the program's text comes from."""

import pytest

from benchmark import manifest as mf
from benchmark.reduce import program_text
from benchmark.reduce import trace as tr

SAMPLE = f"{mf.BENCH_DIR}/reduce/sample/tpu_v5e_three_calls.xplane.pb"

BODY = "jit(epoch_fused)/while/body/closed_call"
BLOCK = "WeatherTransformer/block_0"

#: The sample's four ops, renamed into a step: device-0 time 3 x (4213 +
#: 4214 + 4214) = 12,641 ns in ``fusion.1``, 2,407 + 2,410 + 2,406 = 7,223
#: in ``fusion``, 3 x 13 = 39 in ``copy-start``, 3 x 3 = 9 in
#: ``copy-done``: 19,912 ns in all.
HLO = f'''
HloModule jit_epoch_fused
  %fusion.1 = (bf16[4,256,128]{{2,1,0}}, bf16[2,256,128]{{2,1,0}}) custom-call(%a), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="{BODY}/transpose(jvp(step.loss))/{BLOCK}/attn/pallas_call" stack_frame_id=2}}, backend_config={{"custom_call_config":{{"body":"TUzvUg"}}}}
  %fusion = f32[1024]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{BODY}/step.optimizer/mul"}}
  %copy-start = bf16[4,256,128]{{2,1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/step.eval/{BLOCK}/attn/pallas_call"}}
  ROOT %copy-done = bf16[2,256,512]{{2,1,0}} fusion(%x), kind=kOutput, calls=%fused_computation.3, metadata={{op_name="{BODY}/jvp(step.loss)/{BLOCK}/dense_mlp/ffn_in/dot_general"}}
'''
TOTAL = 19912.0
BY_HAND = {
    "step.optimizer_share": 7223 / TOTAL,
    "step.backward_share": 12641 / TOTAL,
    "step.eval_share": 39 / TOTAL,
    "dense_mlp.share": 9 / TOTAL,
    "attn.share": (12641 + 39) / TOTAL,
    "kernels.flash_fwd_share": 39 / TOTAL,
    "kernels.flash_bwd_share": 12641 / TOTAL,
}


@pytest.fixture(scope="module")
def sample():
    return tr.load(SAMPLE)


def _read(name, art):
    return mf.load_layer_metric(name).read(art)


def test_the_text_comes_from_the_driver_or_the_trainer_s_file(tmp_path):
    # fit_routed keeps the text it saved; driver fit finds the trainer's
    # file beside its trace, under the same work directory.
    assert program_text.text({"hlo_text": HLO}) == HLO
    work = tmp_path / "work"
    art = {"trace_dir": str(work / "trace"), "hlo_text": None}
    assert program_text.text(art) is None  # the parent writes no file
    programs = work / "events" / "programs"
    programs.mkdir(parents=True)
    (programs / "scan_k1.hlo.txt").write_text(HLO)
    assert program_text.path(art) == str(programs / "scan_k1.hlo.txt")
    assert program_text.text(art) == HLO
    # An untraced run has no trace directory, and no reader runs there.
    assert program_text.text({"trace_dir": None}) is None


@pytest.mark.parametrize("name", list(BY_HAND))
def test_each_reader_on_the_sample_by_hand(sample, tmp_path, name):
    assert _read(name, {"trace": sample, "hlo_text": HLO}) == pytest.approx(
        100 * BY_HAND[name], rel=2e-3)
    # The same from the trainer's file.
    programs = tmp_path / "events" / "programs"
    programs.mkdir(parents=True)
    (programs / "scan_k1.hlo.txt").write_text(HLO)
    art = {"trace": sample, "trace_dir": str(tmp_path / "trace")}
    assert _read(name, art) == pytest.approx(100 * BY_HAND[name], rel=2e-3)


@pytest.mark.parametrize("name", list(BY_HAND))
def test_nothing_without_the_scopes_the_names_or_the_text(sample, name):
    bare = HLO.replace("step.", "other.").replace("dense_mlp", "mlp").replace(
        "/attn/", "/mixer/").replace("pallas_call", "custom_call")
    for art in ({"trace": sample, "hlo_text": bare},
                {"trace": sample, "trace_dir": "/nonexistent/trace"},
                {"trace": None, "hlo_text": HLO},
                {"trace": tr.Trace([], [], 0, 1), "hlo_text": HLO}):
        assert _read(name, art) is None


def test_the_flash_calls_are_told_apart_and_grouped_products_left_out():
    def call(name, pair, op_name):
        shape = "(bf16[8]{0}, bf16[8]{0})" if pair else "bf16[8]{0}"
        return (f'  %{name} = {shape} custom-call(%a), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="{op_name}"}}')

    text = "\n".join([
        call("attn.3", True, f"{BODY}/jvp(step.loss)/{BLOCK}/attn/pallas_call"),
        call("attn.5", True,
             f"{BODY}/transpose(jvp(step.loss))/{BLOCK}/attn/pallas_call"),
        call("attn.4", False,
             f"{BODY}/transpose(jvp(step.loss))/{BLOCK}/attn/pallas_call"),
        call("attn.2", False, f"{BODY}/step.eval/{BLOCK}/attn/pallas_call"),
        call("ragged-dot-none.7", False, "ragged-dot-none"),
        call("ragged-dot-metadata.8", True, "ragged-dot-metadata"),
    ])
    assert program_text.flash_kernels(text) == {
        "attn.3": "flash_fwd", "attn.2": "flash_fwd",
        "attn.5": "flash_bwd_dkdv", "attn.4": "flash_bwd_dq"}

    def op(name, start, dur):
        return (f'%{name} = bf16[8]{{0}} custom-call(%a), '
                f'custom_call_target="tpu_custom_call"', start, dur)

    # One step of 100 ns: forward 10 + 2 (validation), dK/dV 20, dQ 15,
    # the grouped products 30 + 3, a fusion 20.
    events = [op("attn.3", 0, 10), op("attn.5", 10, 20), op("attn.4", 30, 15),
              op("attn.2", 45, 2), op("ragged-dot-none.7", 47, 30),
              op("ragged-dot-metadata.8", 77, 3),
              ("%fusion.9 = f32[] fusion(%b), kind=kLoop", 80, 20)]
    art = {"trace": tr.Trace([tr.Device(0, tr.Line(events), tr.Line([]))],
                             [], 0, 100), "hlo_text": text}
    fwd = _read("kernels.flash_fwd_share", art)
    bwd = _read("kernels.flash_bwd_share", art)
    assert (fwd, bwd) == (pytest.approx(12.0), pytest.approx(35.0))
    # kernels.flash_share counts every Mosaic call: flash and the grouped
    # products; what the two new readers leave is the latter.
    assert _read("kernels.flash_share", art) == pytest.approx(80.0)
    assert _read("kernels.flash_share", art) - fwd - bwd == pytest.approx(33)


def test_the_backward_is_the_transpose_of_the_loss_alone():
    assert program_text.transposed(
        f"{BODY}/transpose(jvp(step.loss))/{BLOCK}/attn/dot_general",
        "step.loss")
    # The accumulated path: the same scope inside the microbatch loop.
    assert program_text.transposed(
        f"{BODY}/while/body/transpose(jvp(step.loss))/x", "step.loss")
    assert not program_text.transposed(
        f"{BODY}/jvp(step.loss)/{BLOCK}/attn/dot_general", "step.loss")
    assert not program_text.transposed(
        f"{BODY}/step.optimizer/transpose(x)/mul", "step.loss")
    assert not program_text.transposed(
        f"{BODY}/transpose(jvp(step.losses))/x", "step.loss")


def test_the_entries_name_their_cells():
    entries = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    cells = [c["name"] for c in mf.load_manifest()["workloads"]]
    for name in ("step.optimizer_share", "step.backward_share",
                 "step.eval_share", "kernels.flash_fwd_share",
                 "kernels.flash_bwd_share"):
        assert entries[name]["workloads"] == cells
    sc2 = [c for c in cells if c.startswith("sc2_3b.")]
    assert set(sc2) <= set(entries["dense_mlp.share"]["workloads"])
    assert set(entries["attn.share"]["workloads"]) == set(sc2) | {
        "lfm2_24b_ep8.fit_seq8192_balanced"}
    assert all(entries[n]["layer"] == (
        "kernels" if n.startswith("kernels.") else "step") for n in BY_HAND)
