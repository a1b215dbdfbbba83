"""Multi-process test harness: run a worker fn on N local ranks.

Reference analog: the reference runs test/parallel/* under
``horovodrun -np 2 pytest ...``; we instead spawn ranks in-test so plain
``pytest tests/`` covers distributed behavior (same spirit as the reference's
elastic unit tests that fake workers as threads — SURVEY.md §4).
"""

import multiprocessing as mp
import os
import socket
import sys
import traceback

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _entry(fn, rank, size, port, q, env):
    os.environ.update({
        "HOROVOD_RANK": str(rank),
        "HOROVOD_SIZE": str(size),
        "HOROVOD_LOCAL_RANK": str(rank),
        "HOROVOD_LOCAL_SIZE": str(size),
        "HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
        "HOROVOD_CONTROLLER_PORT": str(port),
        # keep jax off any accelerator inside workers
        "JAX_PLATFORMS": "cpu",
    })
    os.environ.update(env or {})
    sys.path.insert(0, REPO_ROOT)
    # Say it at the config level too (see tests/conftest.py): workers
    # never touch an accelerator.
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except ImportError:
        pass
    try:
        result = fn(rank, size)
        q.put((rank, None, result))
    except Exception as e:  # noqa: BLE001
        traceback.print_exc()
        q.put((rank, f"{type(e).__name__}: {e}", None))


def run_ranks(fn, size, timeout=90, env=None):
    """Run fn(rank, size) on `size` spawned processes; return results by rank.

    Raises AssertionError if any rank fails.
    """
    ctx = mp.get_context("spawn")
    port = free_port()
    q = ctx.Queue()
    procs = [
        ctx.Process(target=_entry, args=(fn, r, size, port, q, env))
        for r in range(size)
    ]
    for p in procs:
        p.start()
    results = {}
    errors = {}
    try:
        for _ in range(size):
            rank, err, res = q.get(timeout=timeout)
            if err is not None:
                errors[rank] = err
            results[rank] = res
    finally:
        for p in procs:
            p.join(timeout=15)
            if p.is_alive():
                p.terminate()
    assert not errors, f"rank failures: {errors}"
    return [results[r] for r in range(size)]
