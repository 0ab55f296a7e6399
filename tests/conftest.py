"""Test rig: SPMD on a virtual 8-device CPU mesh.

The reference's only distributed test rig is two Docker containers on one
machine bridged by gloo (docker-compose.yml:115-151; SURVEY §4). The
TPU-native analog is ``--xla_force_host_platform_device_count=8`` — eight
XLA CPU devices in one process — which exercises the *same compiled
collectives* the TPU path uses, with zero containers.

Must run before jax initializes its backends, hence module scope here.
"""

import os
import sys

# Tests run on CPU: the rig needs 8 virtual devices and f32 numerics for
# the torch-parity assertions. JAX_PLATFORMS is how a process asks for
# CPU; it must be set before jax initializes its backends. A compile
# cache placed from outside (JAX_COMPILATION_CACHE_DIR) would arm the AOT
# store in every test — tests that want one set their own.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def weather_csv(tmp_path_factory):
    from dct_tpu.data.synthetic import generate_weather_csv

    path = tmp_path_factory.mktemp("raw") / "weather.csv"
    return generate_weather_csv(str(path), rows=800, seed=7)


@pytest.fixture(scope="session")
def processed_dir(weather_csv, tmp_path_factory):
    from dct_tpu.etl.preprocess import preprocess_csv_to_parquet

    out = tmp_path_factory.mktemp("processed")
    preprocess_csv_to_parquet(weather_csv, str(out))
    return str(out)


@pytest.fixture(scope="session")
def weather_data(processed_dir):
    from dct_tpu.data.dataset import load_processed_dataset

    return load_processed_dataset(processed_dir)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
