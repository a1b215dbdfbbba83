"""Multi-process test harness: run a worker fn on N local ranks.

Reference analog: the reference runs test/parallel/* under
``horovodrun -np 2 pytest ...``; we instead spawn ranks in-test so plain
``pytest tests/`` covers distributed behavior (same spirit as the reference's
elastic unit tests that fake workers as threads — SURVEY.md §4).

Two ways to the ranks. ``run_ranks`` spawns a world for ONE worker fn
and joins it: for a body whose world is the thing under test (it kills
a rank, re-initialises, sets the environment before ``init``). ``World``
keeps the ranks up and hands them body after body: for a module whose
bodies each take under a second and whose start (spawn, import
TensorFlow, ``init``) takes fifteen. A module-scoped fixture holds it;
each test still runs its own body and reports its own outcome.
"""

import contextlib
import importlib
import multiprocessing as mp
import os
import queue
import socket
import sys
import time
import traceback

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# Ports come from a range of this process's own, BELOW the kernel's
# ephemeral range (32768 up): a port the kernel picked (``bind`` to 0)
# is free only until the next picker asks, and six xdist workers ask
# several times a second between them, while a world derives more ports
# from the one it is given (``xla_ici`` starts jax.distributed's
# coordinator on P + 1, a re-formed ring meets on P + epoch) seconds
# later: "Failed to start RPC server" and a rank silent for 240 s
# (ROADMAP D1). Here a worker steps through its own 3,000 ports sixteen
# at a time, from a start its pid picks (so that two pytest processes
# side by side do not walk in step), and hands out a port whose
# neighbour is free as well.
_PORTS_A_WORKER, _PORT_STEP = 3000, 16
_port_cursor = None


def _bindable(port):
    with socket.socket() as s:
        try:
            s.bind(("127.0.0.1", port))
        except OSError:
            return False
    return True


def free_port():
    global _port_cursor
    worker = os.environ.get("PYTEST_XDIST_WORKER", "")[2:]
    base = 10000 + _PORTS_A_WORKER * (int(worker) % 6 if worker.isdigit()
                                      else 6)     # no xdist: the seventh
    slots = _PORTS_A_WORKER // _PORT_STEP
    if _port_cursor is None:
        _port_cursor = os.getpid() % slots
    for _ in range(slots):
        port = base + _PORT_STEP * (_port_cursor % slots)
        _port_cursor += 1
        if _bindable(port) and _bindable(port + 1):
            return port
    raise RuntimeError(f"no free port in {base}..{base + _PORTS_A_WORKER}")


def _become_rank(rank, size, port, env):
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
    # Say it at the config level too (see tests/conftest.py), to a jax
    # that unpickling this rank's body has imported already: workers
    # never touch an accelerator. A rank that imports jax later reads
    # JAX_PLATFORMS then, and a rank of the eager core never imports it:
    # two seconds of every such rank's start.
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def _entry(fn, rank, size, port, q, env):
    _become_rank(rank, size, port, env)
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


def _serve(frontend, rank, size, port, bodies, answers, env):
    """A rank of a ``World``: import ``frontend`` and ``init()`` once,
    then every body that arrives, each answered with its result or its
    traceback; ``shutdown()`` at the ``None`` that ends the world."""
    _become_rank(rank, size, port, env)
    failed = None
    try:
        hvd = importlib.import_module(frontend)
        hvd.init()
    except Exception:  # noqa: BLE001
        failed = traceback.format_exc()
    while (fn := bodies.get()) is not None:
        if failed:
            answers.put((rank, "the world did not start:\n" + failed, None))
            continue
        try:
            answers.put((rank, None, fn(rank, size)))
        except Exception:  # noqa: BLE001
            answers.put((rank, traceback.format_exc(), None))
    if not failed:
        hvd.shutdown()


class World:
    """``size`` spawned ranks that stay up: each imports ``frontend``
    (``"horovod_tpu.tensorflow"``) and runs its ``init()`` once, then
    ``run(fn)`` runs ``fn(rank, size)`` in every rank, as often as
    asked, and ``close()`` has them ``shutdown()`` and joins them.

    The bodies of one world share its state: the ranks must be handed
    the same bodies in the same order (a fixture does that), and a body
    names its collectives so that no other body's name is met again
    with another shape. A body that fails on a rank may leave the
    others waiting inside a collective, so a failed ``run`` ends the
    world (``alive`` turns False) and the fixture starts another for
    the next test: a failing body fails its own test alone."""

    def __init__(self, frontend, size, env=None):
        ctx = mp.get_context("spawn")
        self.size = size
        self._answers = ctx.Queue()
        self._bodies = [ctx.Queue() for _ in range(size)]
        port = free_port()
        self._procs = [
            ctx.Process(target=_serve, args=(
                frontend, r, size, port, self._bodies[r], self._answers,
                env))
            for r in range(size)]
        for p in self._procs:
            p.start()
        self.alive = True

    def run(self, fn, timeout=90):
        """-> ``fn``'s results by rank. Raises AssertionError if a rank
        fails, dies or does not answer in ``timeout`` seconds (the
        world's start counts towards the first body's)."""
        assert self.alive, "this world has ended"
        for q in self._bodies:
            q.put(fn)
        results, errors = {}, {}
        deadline = time.monotonic() + timeout
        while len(results) < self.size:
            try:
                rank, err, res = self._answers.get(timeout=1)
            except queue.Empty:
                silent = [r for r in range(self.size) if r not in results]
                dead = [r for r in silent if not self._procs[r].is_alive()]
                if dead or time.monotonic() > deadline:
                    errors.update({r: "died" if r in dead else
                                   f"no answer in {timeout} s"
                                   for r in silent})
                    break
                continue
            results[rank] = res
            if err is not None:
                errors[rank] = err
                # the others may wait for this rank inside a collective
                deadline = min(deadline, time.monotonic() + 15)
        if errors:
            self.close()
        assert not errors, "rank failures:\n" + "\n".join(
            f"[rank {r}] {e}" for r, e in sorted(errors.items()))
        return [results[r] for r in range(self.size)]

    def close(self):
        if not self.alive:
            return
        self.alive = False
        for q in self._bodies:
            q.put(None)
        for p in self._procs:
            p.join(timeout=15)
            if p.is_alive():
                p.terminate()


@contextlib.contextmanager
def worlds(frontend, env=None):
    """-> ``world(size)``: the ``World`` of that size on ``frontend``,
    started at first asking, and anew where a failed run ended the last
    one; every one shut down and joined on leaving. The body of a
    module-scoped fixture."""
    made = {}

    def world(size):
        if size not in made or not made[size].alive:
            made[size] = World(frontend, size, env)
        return made[size]

    try:
        yield world
    finally:
        for w in made.values():
            w.close()
