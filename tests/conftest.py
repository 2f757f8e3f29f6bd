"""Test harness: multi-device CPU mesh, mirroring the reference's strategy.

The reference tests distributed behavior with no cluster by running real
multi-subtask pipelines on Flink's local mini-cluster inside one JVM
(SURVEY.md §4). The TPU-native analog: 8 virtual CPU devices via
``--xla_force_host_platform_device_count=8`` so every collective in the
store/driver runs against a real 8-way mesh.

JAX reads its platform and the device count when it is first imported and
initialised, so the environment is set here, at conftest import — before
any test module (or fps_tpu) imports jax. Test subprocesses inherit it.
"""

import importlib.util
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Repo root on sys.path so `import fps_tpu` works without an install step.
sys.path.insert(0, _ROOT)


def _hostenv():
    # Load by file path, NOT `import fps_tpu...`: the package __init__ pulls
    # in jax, which must not be imported before the environment is set.
    spec = importlib.util.spec_from_file_location(
        "_fps_hostenv", os.path.join(_ROOT, "fps_tpu", "utils", "hostenv.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


if "jax" in sys.modules:
    raise pytest.UsageError(
        "jax was imported before tests/conftest.py could select the "
        "8-device CPU mesh (a plugin or PYTHONSTARTUP imported it)"
    )
os.environ.update(_hostenv().cpu_mesh_env(8))


def pytest_configure(config):
    # tier-1 runs with ``-m 'not slow'``; chaos subprocess scenarios that
    # exceed its budget carry @pytest.mark.slow.
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run"
    )


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, (
        f"expected 8 virtual CPU devices, got {len(devs)} ({jax.default_backend()})"
    )
    return devs
