"""Shared pytest config.

Mirrors the reference's test substrate choice (SURVEY.md §4): everything is
testable with a handful of local CPU processes / virtual devices. We force
JAX onto the CPU platform with 8 virtual devices so mesh/sharding tests
(`jax.sharding.Mesh` over dp/tp/sp axes) run without TPU hardware — the same
code path the driver's `dryrun_multichip` validates.
"""

import contextlib
import os
import subprocess
import sys

# Must be set before jax initializes a backend. Forced (not setdefault):
# on a machine that holds a chip jax defaults to the TPU, and tests want
# 8 virtual CPU devices everywhere (the chip is reached only through
# chip_smoke.py). The jax.config update below says the same to a jax
# that was imported before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (after the env setup above, before any backend use)
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# hvdlint fixtures (hvdlint / hvdlint_shipped) for every test file —
# see horovod_tpu/analysis/pytest_plugin.py.
pytest_plugins = ("horovod_tpu.analysis.pytest_plugin",)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ensure_core_built():
    """Build the native core (csrc/ -> horovod_tpu/lib/) if missing/stale."""
    subprocess.run(
        ["make", "-s", "core"], cwd=REPO_ROOT, check=True,
        stdout=subprocess.DEVNULL,
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "quick: sub-5-minute CI lane — core runtime, one multi-rank "
        "file, one elastic path (make test-quick)")
    config.addinivalue_line(
        "markers",
        "loadflaky: timing-sensitive under a loaded box (multi-process "
        "steady-state assertions); runs with widened slack, and a busy "
        "CI shard may deselect with -m 'not loadflaky'")
    config.addinivalue_line(
        "markers",
        "slow: heavyweight lanes (e.g. the 256-rank simulated world) "
        "excluded from the tier-1 budget via -m 'not slow'; covered by "
        "the full suite")
    _ensure_core_built()


_LAUNCHER_ENV = ("HOROVOD_", "OMPI_", "SLURM_")


@contextlib.contextmanager
def launcher_env_restored():
    """On leaving, every ``os.environ`` name under a launcher's prefix
    is as it was on entering: written ones gone, changed ones back."""
    before = {k: v for k, v in os.environ.items()
              if k.startswith(_LAUNCHER_ENV)}
    try:
        yield
    finally:
        for k in [k for k in os.environ if k.startswith(_LAUNCHER_ENV)]:
            if k not in before:
                del os.environ[k]
        os.environ.update(before)


@pytest.fixture(autouse=True)
def _launcher_env_restored():
    """What a test leaves in ``os.environ`` under a launcher's prefix
    goes with the test: ``hvd.init()`` translates ``OMPI_*``/``SLURM_*``
    into ``HOROVOD_RANK``/``SIZE``/... by writing ``os.environ``, which
    ``monkeypatch`` cannot undo, and every later test of this xdist
    worker whose child inherits a rank then fails in company."""
    with launcher_env_restored():
        yield


def pytest_collection_modifyitems(config, items):
    # Keep deterministic ordering: single-process unit tests first.
    items.sort(key=lambda it: ("parallel" in str(it.fspath), str(it.fspath)))


sys.path.insert(0, REPO_ROOT)
