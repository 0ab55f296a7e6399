"""Start-up contract: the platform is whatever JAX selects, the compile
cache is placed from outside, one process for each chip, and
``chip_smoke.py`` refuses to pretend.

The smoke's phases run here at toy size on the 8-device CPU rig with
``DCT_FLASH=interpret`` — the same control flow the chip runs at full
size, one test per phase so a failure names the phase.
"""

import os
import subprocess
import sys
import types

import pytest

import chip_smoke

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------
# chip_smoke.py refuses anything but a TPU


def test_smoke_main_refuses_cpu(capsys):
    assert chip_smoke.main() == 2
    out = capsys.readouterr()
    assert "'cpu'" in out.err and "needs a TPU" in out.err
    assert out.out == ""  # no device line, no JSON result


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it must fail too: it has
    no fallback implementation of anything it checks."""
    with open(os.path.join(_REPO_ROOT, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "JAX_PLATFORMS")
    }
    # Stand in for the chip: a fake jax that reports one TPU device, so
    # the run gets past the platform gate to the first dct_tpu import.
    fake = tmp_path / "jax"
    fake.mkdir()
    (fake / "__init__.py").write_text(
        "__version__ = '0'\n"
        "class _D:\n"
        "    platform = 'tpu'\n"
        "    device_kind = 'fake'\n"
        "def devices():\n"
        "    return [_D()]\n"
    )
    (tmp_path / "jaxlib.py").write_text("__version__ = '0'\n")
    res = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert res.returncode not in (0, 2), res.stderr[-400:]
    assert "dct_tpu" in res.stderr
    assert '"ok"' not in res.stdout


# ----------------------------------------------------------------------
# one compile-cache resolver


@pytest.fixture()
def cache_env(monkeypatch):
    monkeypatch.delenv("DCT_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    from dct_tpu.compilecache import cache

    return cache


def _record_config_updates(monkeypatch) -> list:
    import jax

    calls: list = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.append((k, v))
    )
    return calls


def test_resolver_honours_the_standard_variable(cache_env, monkeypatch):
    assert cache_env.resolve_cache_dir() is None  # auto, nothing placed
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert cache_env.resolve_cache_dir() == "/some/dir"
    assert cache_env.enabled() and cache_env.aot_enabled()
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    assert cache_env.resolve_cache_dir() is None


def test_placed_dir_is_never_set_in_code(cache_env, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set JAX has read it already:
    the resolver arms the cache without naming a directory itself."""
    calls = _record_config_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    for mode in ("auto", "on"):
        monkeypatch.setenv("DCT_COMPILE_CACHE", mode)
        assert cache_env.enable_from_env() == "/some/dir"
    assert calls and all(k != "jax_compilation_cache_dir" for k, _ in calls)


def test_default_dir_is_in_the_checkout_and_set_in_code(
    cache_env, monkeypatch
):
    calls = _record_config_updates(monkeypatch)
    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    want = os.path.join(_REPO_ROOT, ".jax_cache")
    assert cache_env.enable_from_env() == want
    assert ("jax_compilation_cache_dir", want) in calls


def test_cache_off_touches_nothing(cache_env, monkeypatch):
    calls = _record_config_updates(monkeypatch)
    assert cache_env.enable_from_env() is None  # auto, nothing placed
    monkeypatch.setenv("DCT_COMPILE_CACHE", "off")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert cache_env.enable_from_env() is None
    assert calls == []


def test_default_dir_does_not_move_with_the_working_directory(tmp_path):
    """The path is part of every cache key: the same absolute
    in-checkout directory from two different working directories."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]);"
        "from dct_tpu.compilecache import resolve_cache_dir;"
        "print(resolve_cache_dir())"
    )
    env = dict(os.environ, DCT_COMPILE_CACHE="on")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    seen = set()
    for cwd in (tmp_path, _REPO_ROOT):
        res = subprocess.run(
            [sys.executable, "-c", code, _REPO_ROOT], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=60, check=True,
        )
        seen.add(res.stdout.strip())
    assert seen == {os.path.join(_REPO_ROOT, ".jax_cache")}


def test_supervisor_pins_the_standard_variable_into_relaunches(cache_env):
    child = {"DCT_COMPILE_CACHE": "on"}
    cache_env.export_env(child, {})
    assert child["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
        _REPO_ROOT, ".jax_cache"
    )
    placed = {"JAX_COMPILATION_CACHE_DIR": "rel/dir"}
    cache_env.export_env(placed, {})
    assert placed["JAX_COMPILATION_CACHE_DIR"] == "rel/dir"  # theirs wins
    inherited: dict = {}
    cache_env.export_env(inherited, {"JAX_COMPILATION_CACHE_DIR": "rel/dir"})
    assert inherited["JAX_COMPILATION_CACHE_DIR"] == os.path.abspath("rel/dir")
    off: dict = {}
    cache_env.export_env(off, {})
    assert off == {}


def test_removed_knobs_are_gone():
    from dct_tpu.config import ENV_REGISTRY

    # Spelled in two halves so a grep for the removed names stays empty.
    for gone in (
        "REQUIRE_TPU", "BACKEND_PROBE_TIMEOUT", "BACKEND_PROBE_BUDGET",
        "BACKEND_PROBE_RETRIES", "JAX_CACHE", "JAX_CACHE_DIR",
        "COMPILE_CACHE_DIR",
    ):
        assert "DCT_" + gone not in ENV_REGISTRY
    assert not os.path.exists(
        os.path.join(_REPO_ROOT, "dct_tpu", "utils", "platform.py")
    )
    assert not os.path.exists(
        os.path.join(_REPO_ROOT, "dct_tpu", "parallel", "shard_map_" "compat.py")
    )


def test_nothing_pins_its_own_process_to_cpu():
    """JAX_PLATFORMS=cpu is how a CALLER asks for CPU. The program may
    put it into a child's environment (a dict literal / keyword), never
    into its own."""
    import re

    own = re.compile(
        r"os\.environ\[\s*['\"]JAX_PLATFORMS['\"]\s*\]\s*="
        r"|os\.environ\.setdefault\(\s*['\"]JAX_PLATFORMS['\"]"
        r"|os\.putenv\(\s*['\"]JAX_PLATFORMS['\"]"
        r"|config\.update\(\s*['\"]jax_platforms['\"]"
    )
    roots = ["dct_tpu", "jobs"]
    files = [
        os.path.join(_REPO_ROOT, "__graft_entry__.py"),
        os.path.join(_REPO_ROOT, "chip_smoke.py"),
    ]
    for root in roots:
        for d, _dirs, names in os.walk(os.path.join(_REPO_ROOT, root)):
            files += [os.path.join(d, n) for n in names if n.endswith(".py")]
    offenders = []
    for path in files:
        with open(path) as f:
            if own.search(f.read()):
                offenders.append(os.path.relpath(path, _REPO_ROOT))
    assert offenders == []


# ----------------------------------------------------------------------
# one process for each chip


@pytest.mark.parametrize("module", [
    "dct_tpu.resilience.supervise",
    "dct_tpu.continuous.loop",
    "dct_tpu.scheduler.scheduler",
    "dct_tpu.utils.chip",
])
def test_parent_side_modules_stay_off_jax(module):
    """The supervisor, loop and scheduler parents must not hold the chip
    their child trainers need: importing them leaves jax unimported."""
    code = (
        f"import sys; sys.path.insert(0, {_REPO_ROOT!r}); "
        f"import {module}; "
        "sys.exit(1 if 'jax' in sys.modules else 0)"
    )
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60,
    )
    assert res.returncode == 0, f"{module} imported jax\n{res.stderr[-300:]}"


def test_refuse_shared_chip_reads_the_environment():
    from dct_tpu.utils.chip import (
        ChipContentionError,
        pinned_to_cpu,
        refuse_shared_chip,
    )

    assert pinned_to_cpu({"JAX_PLATFORMS": "cpu"})
    assert pinned_to_cpu({"JAX_PLATFORMS": " CPU ,tpu"})
    assert not pinned_to_cpu({})
    assert not pinned_to_cpu({"JAX_PLATFORMS": "tpu,cpu"})
    refuse_shared_chip("drill", {"JAX_PLATFORMS": "cpu"})
    with pytest.raises(ChipContentionError, match="two trainers"):
        refuse_shared_chip("two trainers", {"JAX_PLATFORMS": ""})


def test_serve_pool_with_jax_engine_refuses_before_forking(monkeypatch):
    from dct_tpu.utils.chip import ChipContentionError
    from jobs import serve

    forks: list = []
    monkeypatch.setattr(os, "fork", lambda: forks.append(1) or 1)
    monkeypatch.delenv("JAX_PLATFORMS")
    serving = types.SimpleNamespace(
        engine="jax", processes=2, max_restarts=0, autoscale=False
    )
    with pytest.raises(ChipContentionError, match="DCT_SERVE_PROCS=2"):
        serve._serve_pool(lambda *a, **k: None, "x", serving, "127.0.0.1", 0)
    assert forks == []


def test_supervised_loop_refuses_parent_side_scorer_warmup(
    tmp_path, monkeypatch
):
    from dct_tpu.config import LoopConfig, ObservabilityConfig, RunConfig
    from dct_tpu.continuous.loop import AlwaysOnLoop
    from dct_tpu.utils.chip import ChipContentionError

    monkeypatch.setenv("DCT_COMPILE_CACHE", "on")
    monkeypatch.setenv("DCT_COMPILE_CACHE_WARM_SIZES", "1")
    monkeypatch.delenv("JAX_PLATFORMS")
    cfg = RunConfig(
        obs=ObservabilityConfig(events_dir=str(tmp_path / "events")),
        loop=LoopConfig(
            train_mode="supervised", packages_dir=str(tmp_path / "pkgs"),
        ),
    )
    with pytest.raises(ChipContentionError, match="supervised"):
        AlwaysOnLoop(cfg).run()


def test_healthcheck_runs_in_the_foreground():
    """The per-host ``jax.devices()`` check takes the chips for a
    moment; it must have exited before the launch block starts rank 0."""
    from dct_tpu.launch.launcher import build_healthcheck_script

    script = build_healthcheck_script(["h0", "h1"])
    for line in script.splitlines():
        assert not line.rstrip().endswith("&"), line
        assert "nohup" not in line


# ----------------------------------------------------------------------
# nothing hides the device


def test_interpret_mode_on_tpu_only_by_explicit_request(monkeypatch):
    import jax

    from dct_tpu.ops import attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mode in ("auto", "on", "1"):
        monkeypatch.setenv("DCT_FLASH", mode)
        assert attention.flash_interpret_mode() is False
        assert attention._resolve_flash(None) == (True, False)
        assert attention._resolve_flash(True) == (True, False)
    monkeypatch.setenv("DCT_FLASH", "off")
    assert attention.flash_interpret_mode() is None
    assert attention._resolve_flash(True) == (True, False)
    monkeypatch.setenv("DCT_FLASH", "interpret")
    assert attention.flash_interpret_mode() is True


def test_no_peak_off_the_tpu_and_no_stand_in(monkeypatch):
    from dct_tpu.observability import roofline
    from dct_tpu.utils.profiling import chip_peak_flops

    monkeypatch.delenv("DCT_PEAK_TFLOPS", raising=False)
    assert chip_peak_flops() is None
    assert roofline.resolve_peak_flops() == (None, "not_measured")
    assert not hasattr(roofline, "measure_host_peak_flops")
    monkeypatch.setenv("DCT_PEAK_TFLOPS", "2")
    assert roofline.resolve_peak_flops() == (2e12, "DCT_PEAK_TFLOPS")


def test_unknown_tpu_device_kind_is_an_error(monkeypatch):
    import jax

    from dct_tpu.utils.profiling import chip_peak_flops

    monkeypatch.delenv("DCT_PEAK_TFLOPS", raising=False)
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    with pytest.raises(ValueError, match="TPU v99"):
        chip_peak_flops()
    dev.device_kind = "TPU v5 lite"
    assert chip_peak_flops() == 197e12


# ----------------------------------------------------------------------
# the smoke's phases, toy size, in order (module-scoped state)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The work dir, the environment the phases need, and what earlier
    phases hand to later ones."""
    work = str(tmp_path_factory.mktemp("chip_smoke"))
    saved = {
        k: os.environ.get(k)
        for k in ("DCT_FLASH", "JAX_COMPILATION_CACHE_DIR")
    }
    os.environ["DCT_FLASH"] = "interpret"
    # A cold cache dir of the test's own, placed from outside like an
    # operator would.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work, "cache")
    cwd = os.getcwd()
    os.chdir(work)
    try:
        yield {"work": work, "size": chip_smoke.TOY}
    finally:
        os.chdir(cwd)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_toy_etl(toy):
    toy["processed"] = chip_smoke.phase_etl(toy["work"], toy["size"].rows)


def test_toy_mlp_fit_and_checkpoints(toy):
    size = toy["size"]
    toy["mlp"] = chip_smoke.phase_fit(
        toy["work"], "mlp", toy["processed"],
        {**size.fit_env, "DCT_EPOCHS": size.mlp_epochs},
    )
    assert toy["mlp"]["mesh"]["data"] == 8


def test_toy_mlp_served_by_the_jitted_scorer(toy):
    chip_smoke.phase_serve(toy["work"], "mlp", toy["mlp"], platform="cpu")


def test_toy_attention_path_is_flash(toy, monkeypatch):
    from dct_tpu.config import ModelConfig

    cfg = ModelConfig(
        name="weather_transformer_causal", **toy["size"].transformer
    )
    chip_smoke.assert_flash_path(cfg, batch=2, input_dim=5)
    # Without the explicit opt-in the CPU has no flash path at all, and
    # the assertion says so instead of passing on another path.
    monkeypatch.setenv("DCT_FLASH", "auto")
    with pytest.raises(chip_smoke.SmokeFailure, match="not flash"):
        chip_smoke.assert_flash_path(cfg, batch=2, input_dim=5)


def test_toy_transformer_fit_and_checkpoints(toy):
    toy["tf"] = chip_smoke.phase_fit(
        toy["work"], "transformer", toy["processed"],
        chip_smoke._transformer_env(toy["size"]),
    )


def test_toy_transformer_served_by_the_jitted_scorer(toy):
    chip_smoke.phase_serve(
        toy["work"], "transformer", toy["tf"], platform="cpu"
    )


def test_toy_kernel_table(toy):
    chip_smoke.phase_kernels(toy["size"].kernels)


def test_toy_multi_device_legs(toy):
    chip_smoke.phase_multichip(toy["work"], toy["processed"], toy["size"])


def test_a_phase_confined_to_device_zero_fails(toy):
    result = types.SimpleNamespace(placement={"state": [0], "batch": [0]})
    with pytest.raises(chip_smoke.SmokeFailure, match="left devices idle"):
        chip_smoke.check_placement("x", {"data": 8}, result)
