"""Every rank of a multi-rank job keeps what it compiles.

jax 0.9.0 writes a persistent compile-cache entry only in the process
whose ``jax.distributed`` process id is 0, and ``xla_ici.enable()``
registers rank r as process r, so ranks other than 0 would compile again
on every launch. ``enable_compile_cache()`` makes every process a writer
(``horovod_tpu/utils/compile_cache.py:_let_every_rank_write``) and
leaves ``jax.distributed`` as it is: its client is what orbax's
multi-process checkpoints wait on.

The CPU can show the decision, the seam in the installed jax, and that a
gloo rank other than 0 writes entries a second launch finds. Four TPU
processes doing so is the chip benchmark's four-chip cell.
"""

import logging
import types
from unittest import mock

import numpy as np
import pytest

from horovod_tpu.runner.launch import _tpu_pod_env
from tests.utils_mp import run_ranks

_XLA_PLANE = {"HOROVOD_XLA_DATA_PLANE": "1"}


def _pod_env(local_rank=1, local_size=4):
    """What ``horovodrun --tpu-pod`` exports to one rank."""
    slot = types.SimpleNamespace(local_rank=local_rank,
                                 local_size=local_size, cross_size=1)
    return _tpu_pod_env(slot, list(range(8470, 8470 + local_size)))


@pytest.mark.parametrize("size,pod_env,already_up,initializes", [
    pytest.param(4, True, False, True, id="pod environment"),
    pytest.param(4, False, False, True, id="no pod environment"),
    pytest.param(4, False, True, False, id="jax.distributed already up"),
    pytest.param(4, True, True, False,
                 id="jax.distributed already up, pod environment"),
    pytest.param(1, False, False, False, id="one rank"),
    pytest.param(1, True, False, False, id="one rank, pod environment"),
])
def test_enable_keeps_jax_distributed_for_every_multi_rank_job(
        monkeypatch, size, pod_env, already_up, initializes):
    """Every rank being a writer costs nothing of ``jax.distributed``:
    a multi-rank job gets it as before, under the launcher's pod
    environment too, and nothing of jax's process identity is touched
    where it is up already or where there is one rank."""
    import jax

    from horovod_tpu.jax import xla_ici

    rank = size - 1
    for k in _pod_env():
        monkeypatch.delenv(k, raising=False)
    if pod_env:
        for k, v in _pod_env(rank, size).items():
            monkeypatch.setenv(k, v)
    monkeypatch.setenv("HOROVOD_CONTROLLER_ADDR", "10.1.2.3")
    monkeypatch.setenv("HOROVOD_CONTROLLER_PORT", "29700")
    monkeypatch.delenv("HOROVOD_XLA_COORD_PORT", raising=False)
    monkeypatch.delenv("HOROVOD_CROSS_PLANE", raising=False)
    lib = mock.Mock()
    lib.hvdtpu_is_initialized.return_value = False   # plane mode: the env
    monkeypatch.setattr(xla_ici, "_basics", types.SimpleNamespace(
        rank=lambda: rank, size=lambda: size, lib=lib))
    monkeypatch.setattr(xla_ici, "_distributed_initialized",
                        lambda: already_up)
    # The world a rank would see once its backend is up: `size`
    # processes, all of whose devices this test's one process stands in
    # for (process index 0).
    monkeypatch.setattr(jax, "process_count", lambda: size)
    monkeypatch.setattr(
        xla_ici.eager_ops, "allgather_async",
        lambda *a, **k: types.SimpleNamespace(
            synchronize=lambda: np.zeros(size, np.int32)))
    plane = xla_ici.XlaIciDataPlane()
    with mock.patch.object(jax.distributed, "initialize") as initialize:
        plane.enable()
    try:
        if initializes:
            initialize.assert_called_once_with(
                coordinator_address="10.1.2.3:29701", num_processes=size,
                process_id=rank)
        else:
            initialize.assert_not_called()
        assert plane.active
        assert len(plane._devices) == size
    finally:
        plane.disable()


def test_the_installed_jax_has_the_seam_the_writer_leans_on(monkeypatch):
    """The pin: jax's compile path calls ``_cache_write`` through its
    module after a miss, that function returns where the process id is
    not 0, and ``_let_every_rank_write`` takes its place, handing
    process 0 (this process) on to it."""
    import inspect

    from jax._src import compiler

    from horovod_tpu.utils import compile_cache

    # jax's text, whatever stands in the function's place by now
    source = inspect.getsource(compiler)
    assert "\ndef _cache_write(cache_key: str," in source
    assert "if distributed.global_state.process_id != 0:" in source
    assert "_cache_write(" in source[source.index(
        "def _compile_and_write_cache("):source.index("def _cache_write(")]
    written = []

    def jax_write(cache_key, compile_time_secs, module_name, backend,
                  executable, host_callbacks):
        written.append(cache_key)

    monkeypatch.setattr(compiler, "_cache_write", jax_write)
    compile_cache._let_every_rank_write()
    assert compiler._cache_write is not jax_write
    compiler._cache_write("key", 2.0, "jit_f", None, None, [])
    assert written == ["key"]


@pytest.mark.parametrize("other", [
    pytest.param(lambda cache_key, compile_time_secs, module_name,
                 executable: None, id="other arguments"),
    pytest.param(None, id="gone"),
])
def test_another_jax_is_left_alone_and_told(monkeypatch, caplog, other):
    from jax._src import compiler

    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(compiler, "_cache_write", other)
    with caplog.at_level(logging.WARNING,
                         logger=compile_cache.logger.name):
        compile_cache._let_every_rank_write()
    assert compiler._cache_write is other
    assert "ranks other than 0 will not write" in caplog.text


def _cache_every_program():
    """In a worker: the cache on, and no program too quick or too small
    to be written."""
    import jax

    from horovod_tpu.utils import compile_cache

    compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _worker_compiles_its_own_program(rank, size):
    import jax
    import jax.numpy as jnp
    from jax._src import distributed

    import horovod_tpu.jax as hvd
    from horovod_tpu.utils import compile_cache

    _cache_every_program()
    hvd.init()
    try:
        # A program no other rank compiles: only this rank's own entry
        # from an earlier launch can serve it.
        out = jax.jit(lambda x: jnp.tanh(x) * 3 + rank)(jnp.ones((8, 8)))
        out.block_until_ready()
        total = hvd.allreduce(jnp.full((4,), float(rank)), op=hvd.Sum)
        assert float(total[0]) == sum(range(size))
        return dict(compile_cache.compile_stats(),
                    process_id=distributed.global_state.process_id,
                    coordinated=distributed.global_state.client is not None)
    finally:
        hvd.shutdown()


def test_a_rank_other_than_0_writes_entries_its_next_launch_finds(tmp_path):
    """Two launches of a two-rank job on the device plane (gloo here):
    the second launch's rank 1, jax's process 1, fetches everything the
    first one's rank 1 compiled. (With jax's writer alone rank 1 writes
    nothing and the second launch compiles again.)"""
    env = dict(_XLA_PLANE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    first = run_ranks(_worker_compiles_its_own_program, 2, env=env,
                      timeout=180)
    second = run_ranks(_worker_compiles_its_own_program, 2, env=env,
                       timeout=180)
    for rank, (cold, warm) in enumerate(zip(first, second)):
        assert cold["cache_misses"] >= 1, (rank, cold)
        assert warm["cache_hits"] >= 1 and warm["backend_compiles"] == 0 \
            and warm["cache_misses"] == 0, (rank, warm)
        assert (warm["process_id"], warm["coordinated"]) == (rank, True)


def _worker_compiles_alone(rank, size):
    import warnings

    import jax
    import jax.numpy as jnp

    from horovod_tpu.utils import compile_cache

    _cache_every_program()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = jax.jit(lambda x: jnp.tanh(x) @ x)(jnp.full((8, 8), 0.5))
        value = float(out[0, 0])
    return dict(compile_cache.compile_stats(), value=value,
                warned=[str(w.message) for w in caught
                        if "persistent compilation cache" in str(w.message)])


def test_a_torn_entry_is_compiled_again_not_trusted(tmp_path):
    """Four ranks write the multi-process program under one key, with
    no lock unless a maximum size is set: a reader may meet half a
    file. jax must then compile, as ``compile_cache``'s docstring says
    (nothing here may switch ``jax_raise_persistent_cache_errors`` on)."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    (whole,) = run_ranks(_worker_compiles_alone, 1, env=env, timeout=120)
    entries = [p for p in tmp_path.iterdir() if p.name.endswith("-cache")]
    assert entries and whole["cache_misses"] == len(entries)
    for p in entries:
        p.write_bytes(p.read_bytes()[:p.stat().st_size // 2])
    (torn,) = run_ranks(_worker_compiles_alone, 1, env=env, timeout=120)
    assert torn["value"] == whole["value"]
    assert torn["warned"] and torn["backend_compiles"] >= len(entries), torn


def _worker_checkpoints(rank, size, directory):
    import jax
    import jax.numpy as jnp

    import horovod_tpu.jax as hvd
    from horovod_tpu import checkpoint as ckpt

    hvd.init()
    try:
        assert jax.process_count() == size
        state = {"w": np.arange(8, dtype=np.float32) + 1,
                 "on_chip": jnp.arange(4.0) * 2, "step": np.int64(7)}
        ckpt.save(f"{directory}/one", state, sync=True)
        back = ckpt.restore(f"{directory}/one")
        with ckpt.CheckpointManager(f"{directory}/steps",
                                    max_to_keep=2) as mgr:
            for step in (1, 2, 3):
                mgr.save(step, state)
            mgr.wait()
            latest = mgr.latest_step()
            kept = mgr.restore()
        return {"w": np.asarray(back["w"]).tolist(),
                "on_chip": np.asarray(back["on_chip"]).tolist(),
                "step": int(back["step"]), "latest": latest,
                "kept_w": np.asarray(kept["w"]).tolist()}
    finally:
        hvd.shutdown()


def test_checkpoints_work_where_every_rank_is_a_jax_process(tmp_path):
    """One jax process a rank, as under ``horovodrun --tpu-pod``: orbax
    coordinates the write through ``jax.distributed``'s client, so that
    client has to be there (it is why every rank is made a writer of the
    compile cache without giving ``jax.distributed`` up)."""
    import functools

    results = run_ranks(
        functools.partial(_worker_checkpoints, directory=str(tmp_path)), 2,
        env=_XLA_PLANE, timeout=240)
    for got in results:
        assert got["w"] == [1, 2, 3, 4, 5, 6, 7, 8]
        assert got["on_chip"] == [0, 2, 4, 6]
        assert (got["step"], got["latest"]) == (7, 3)
        assert got["kept_w"] == got["w"]
