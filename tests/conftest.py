"""Shared pytest config, and what a case may cost.

Tier-1 (ROADMAP.md) is the gate of every PR and runs against a time
limit; a run the limit cuts counts only as far as it got. The rule that
keeps it inside: **a case pays only for the question it alone asks.**

1. A reference module (``single/test_*_reference.py``) has ONE base
   configuration, the smallest depth that holds every kind of layer,
   every parameter stack and the share once: the remat sweep, "every
   gradient leaf", the slices, the loads and the planted faults compile
   THAT; the deeper (two-period) shape is one case, under the cell's own
   remat mode. What differs only in data (seed, load, bias, batch) is an
   argument of the compiled program, never a new static configuration.
2. The older configurations are pinned in one place: a model_config PR
   adds a ROW to ``single/test_older_configurations.py``, not a copy of
   its table.
3. Ranks that only serve bodies start once a module: ``utils_mp.World``
   behind a module-scoped ``*_world`` fixture, each test handing them
   its own body. A world of its own (``run_ranks``) where the world is
   the thing under test, with a line that says why.
4. A ``for`` over modes, blocks or shapes is ``pytest.mark.parametrize``
   over a module-scoped fixture that holds the operands and the
   reference: each reports, fails and is scheduled on its own.
5. A kernel's file shares one set of operands and one reference
   evaluation; interpret-mode shapes are the smallest that cross a tile,
   a chunk and a window boundary, the boundary named beside the shape.
   The reference math and ``jax.grad`` of it run under ``jax.jit``, one
   program a side where values and gradients are both read: evaluated
   eagerly they are a compile a primitive, three or four times what the
   kernel's own case costs. A file keeps ONE eager call, named as such,
   because users make it too.

A module's cost: ``pytest <file> -n 0 --durations=0``, parent beside
change. Not a way there: ``slow``, ``skip``, ``xfail``, a timeout, a
deleted case, a compile cache shared between the workers.

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
    # Deterministic order: the single-process unit tests, then the
    # multi-process ones. Before both, the modules that share the
    # dearest set-up: the ``*_world`` fixtures' (fifteen seconds a
    # world), then the reference modules' (a float32 reference compiled
    # once a module). A HEURISTIC, measured in one whole run (PR 59: the
    # TensorFlow world and xing4's reference each set up once): xdist's
    # load scheduler hands out its largest chunks first and chunks of
    # two at the end, and what a module shares is set up again by every
    # worker that is handed one of its cases, so early modules meet
    # fewer workers. Nothing aligns a module to a chunk (a long module
    # still straddles two), the chunk sizes are xdist's internals, and
    # no test may depend on its module reaching one worker.
    def shares(it):
        if any(name.endswith("_world") for name in it.fixturenames):
            return 0
        return 1 if it.fspath.basename.endswith("_reference.py") else 2

    items.sort(key=lambda it: (shares(it), "parallel" in str(it.fspath),
                               str(it.fspath)))


sys.path.insert(0, REPO_ROOT)
