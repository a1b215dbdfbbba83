"""Elastic subsystem unit tests: discovery, rendezvous, driver, state.

Reference analog: test/single/elastic/ (test_driver.py, test_rendezvous.py)
— fake discovery scripts and thread-fake workers exercise multi-node logic
without a cluster (SURVEY.md §4).
"""

import os
import stat
import threading
import time

import numpy as np
import pytest

from horovod_tpu.runner.elastic.discovery import (
    FixedHosts,
    HostDiscoveryScript,
    HostManager,
)
from horovod_tpu.runner.elastic.rendezvous import (
    RendezvousClient,
    RendezvousServer,
)
from horovod_tpu.runner.elastic.worker import (
    WorkerNotificationManager,
    notify_worker,
)

# Part of the sub-5-minute CI lane (make test-quick).
pytestmark = pytest.mark.quick


def _script(tmp_path, hosts_file):
    path = tmp_path / "discover.sh"
    path.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    path.chmod(path.stat().st_mode | stat.S_IEXEC)
    return str(path)


def test_discovery_script_parsing(tmp_path):
    hosts_file = tmp_path / "hosts"
    hosts_file.write_text("node1:4\nnode2:2\n# comment\nnode3\n")
    disc = HostDiscoveryScript(_script(tmp_path, hosts_file),
                               default_slots=3)
    assert disc.find_available_hosts_and_slots() == {
        "node1": 4, "node2": 2, "node3": 3}


def test_host_manager_change_detection_and_blacklist(tmp_path):
    hosts_file = tmp_path / "hosts"
    hosts_file.write_text("a:2\n")
    mgr = HostManager(HostDiscoveryScript(_script(tmp_path, hosts_file)))
    changed, added, removed = mgr.update_available_hosts()
    assert changed and added == ["a"] and not removed
    assert mgr.slot_count() == 2

    hosts_file.write_text("a:2\nb:1\n")
    changed, added, removed = mgr.update_available_hosts()
    assert changed and added == ["b"]

    hosts_file.write_text("b:1\n")
    changed, added, removed = mgr.update_available_hosts()
    assert changed and removed == ["a"]

    mgr.blacklist("b")
    mgr.update_available_hosts()
    assert mgr.current_hosts == {}
    assert mgr.is_blacklisted("b")


def test_rendezvous_assignment_epochs():
    server = RendezvousServer()
    try:
        client = RendezvousClient("127.0.0.1", server.port)
        client.register("w0", "localhost", 0, None)
        client.register("w1", "localhost", 1, None)
        assert set(server.registered_workers()) == {"w0", "w1"}

        # No epoch cut yet -> polling times out.
        with pytest.raises(TimeoutError):
            client.poll_assignment("w0", timeout=0.5)

        server.start_epoch({
            "w0": {"rank": 0, "size": 2},
            "w1": {"rank": 1, "size": 2},
        })
        asg = client.poll_assignment("w0", timeout=5)
        assert asg["rank"] == 0 and asg["epoch"] == 1

        # A worker that consumed epoch 1 must NOT re-adopt it after a
        # failure; it waits for epoch 2.
        with pytest.raises(TimeoutError):
            client.poll_assignment("w0", timeout=0.5, min_epoch=2)
        server.start_epoch({"w0": {"rank": 0, "size": 1}})
        asg = client.poll_assignment("w0", timeout=5, min_epoch=2)
        assert asg["epoch"] == 2 and asg["size"] == 1

        client.kv_put("k", {"v": 1})
        assert client.kv_get("k") == {"v": 1}
        assert client.kv_get("missing") is None
    finally:
        server.stop()


def test_worker_notification_roundtrip():
    mgr = WorkerNotificationManager()
    port = mgr.init()
    try:
        assert mgr.poll_hosts_updated() == (False, False)
        assert notify_worker("127.0.0.1", port, skip_sync=True)
        deadline = time.monotonic() + 5
        updated = skip = False
        while time.monotonic() < deadline and not updated:
            updated, skip = mgr.poll_hosts_updated()
        assert updated and skip
        # Flag is consumed.
        assert mgr.poll_hosts_updated() == (False, False)
    finally:
        mgr.shutdown()


def test_driver_spawns_and_cuts_epoch(tmp_path):
    """Thread-fake workers: the spawned command registers with rendezvous
    and exits 0; the driver must cut an epoch covering every slot."""
    marker = tmp_path / "assignments"
    marker.mkdir()
    worker_src = tmp_path / "worker.py"
    worker_src.write_text(f"""
import json, os, sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))!r})
from horovod_tpu.runner.elastic.rendezvous import RendezvousClient

wid = os.environ["HOROVOD_WORKER_ID"]
c = RendezvousClient(os.environ["HOROVOD_RDZV_ADDR"],
                     os.environ["HOROVOD_RDZV_PORT"])
c.register(wid, os.environ["HOROVOD_HOSTNAME"], 0, None)
asg = c.poll_assignment(wid, timeout=30)
open(os.path.join({str(marker)!r}, wid.replace(":", "_")), "w").write(
    json.dumps(asg))
""")
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    import sys

    driver = ElasticDriver(FixedHosts({"localhost": 3}),
                           [sys.executable, str(worker_src)], min_np=3)
    driver.start()
    try:
        rc = driver.wait_for_completion()
    finally:
        driver.stop()
    assert rc == 0
    import json

    got = sorted(json.loads(p.read_text())["rank"]
                 for p in marker.iterdir())
    assert got == [0, 1, 2]
    sizes = {json.loads(p.read_text())["size"] for p in marker.iterdir()}
    assert sizes == {3}


def _reconcile_driver(hosts):
    """ElasticDriver with fake spawn/cut, for reconcile-logic tests."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver, _Worker

    d = ElasticDriver.__new__(ElasticDriver)
    d._lock = threading.RLock()
    d._min_np = 1
    d._max_np = 10 ** 9
    d._start_timeout = 5
    d._final_codes = []
    d._reconcile_needed = threading.Event()
    d._verbose = False
    d._rendezvous = RendezvousServer()
    d._workers = {}
    d._host_failures = {}
    d._shutdown = threading.Event()

    class _Mgr:
        current_hosts = dict(hosts)

    d._manager = _Mgr()
    d._spawned = []
    d._cuts = []

    def fake_spawn(host, idx):
        w = _Worker(f"{host}:{len(d._spawned)}-{idx}", host, idx)
        d._workers[w.worker_id] = w
        d._spawned.append(w)
        return w

    d._spawn = fake_spawn
    d._cut_epoch = lambda workers, **kw: d._cuts.append(list(workers))
    return d


def test_reconcile_shrink_respects_host_capacity():
    """fail→respawn→shrink: a surviving oldest worker may hold
    local_index >= slots; the freed lower index must NOT be refilled on
    a host already at capacity (would publish local_size > slots and
    double-bind chips)."""
    d = _reconcile_driver({"h": 4})
    try:
        d._reconcile()
        assert len(d._workers) == 4
        # idx2 fails; its slot frees; the respawn takes it (youngest seq)
        dead = next(w for w in d._workers.values() if w.local_index == 2)
        del d._workers[dead.worker_id]
        d._reconcile()
        assert len(d._workers) == 4
        # shrink to 3 slots: the respawn (youngest) dies; survivors hold
        # indexes {0, 1, 3}; index 2 is free but the host is full.
        d._manager.current_hosts = {"h": 3}
        spawns_before = len(d._spawned)
        d._reconcile()
        assert len(d._workers) == 3
        assert len(d._spawned) == spawns_before
        assert {w.local_index for w in d._workers.values()} == {0, 1, 3}
    finally:
        d._rendezvous.stop()


def test_reconcile_skips_ghost_epoch_when_fleet_unchanged():
    """A reconcile that spawns nothing, kills nothing, and covers no
    re-registration must not cut an epoch (a ghost epoch desyncs the
    next real recovery's last_epoch tracking)."""
    d = _reconcile_driver({"h": 2})
    try:
        d._reconcile()
        assert len(d._cuts) == 1
        d._reconcile()  # discovery delta with no usable change
        assert len(d._cuts) == 1
        d._reconcile(force_cut=True)  # re-registration / retry: must cut
        assert len(d._cuts) == 2
    finally:
        d._rendezvous.stop()


def test_object_state_commit_restore():
    from horovod_tpu.common.elastic import ObjectState

    state = ObjectState(step=0, weights=np.zeros(3))
    state.step = 5
    state.weights = state.weights + 2
    state.save()
    state.step = 9
    state.weights[:] = 99
    state.restore()
    assert state.step == 5
    np.testing.assert_allclose(state.weights, 2)


def test_cut_epoch_rank_layout_survivor_first():
    """Rank 0 is the longest-lived worker; layout is host-major and
    cross_rank agrees with rank // local_size (the hierarchical
    allreduce probe's invariant), regardless of host name order."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver, _Worker

    driver = ElasticDriver.__new__(ElasticDriver)
    driver._lock = threading.RLock()
    driver._min_np = 1
    driver._start_timeout = 5
    driver._final_codes = []
    driver._reconcile_needed = threading.Event()
    driver._verbose = False
    driver._rendezvous = RendezvousServer()
    try:
        # 'zeta' host holds the two oldest workers (incl. the original
        # rank 0); 'alpha' got a fresh respawn (highest seq).
        workers = [_Worker("zeta:a", "zeta", 0),
                   _Worker("zeta:b", "zeta", 1),
                   _Worker("alpha:c", "alpha", 0),
                   _Worker("alpha:d", "alpha", 1)]
        # respawn on alpha slot 0: new uuid, max seq
        respawn = _Worker("alpha:e", "alpha", 0)
        fleet = [workers[0], workers[1], respawn, workers[3]]
        driver._workers = {w.worker_id: w for w in fleet}
        client = RendezvousClient("127.0.0.1", driver._rendezvous.port)
        for w in fleet:
            client.register(w.worker_id, w.host, w.local_index, None)
        driver._cut_epoch(fleet)

        asg = {w.worker_id: client.poll_assignment(w.worker_id, timeout=5)
               for w in fleet}
        # oldest worker (zeta:a) is rank 0 even though 'alpha' < 'zeta'
        assert asg["zeta:a"]["rank"] == 0
        # fresh respawn is ranked last within its host
        assert asg["alpha:e"]["rank"] > asg["alpha:d"]["rank"]
        for a in asg.values():
            assert a["size"] == 4 and a["local_size"] == 2
            assert a["cross_rank"] == a["rank"] // a["local_size"]
            assert a["cross_size"] == 2
    finally:
        driver._rendezvous.stop()


def test_cut_epoch_deals_no_rank_to_an_exit_it_has_not_reaped():
    """A worker dies and the survivor re-registers BEFORE the driver's
    reaper thread has taken the dead worker out of the fleet
    (``ElasticDriver._cut_epoch``'s ``asked_only``; the cause of
    tests/integration/test_elastic_keras.py's two failures in company:
    both ranks re-entered at epoch 0). The cut waits for the reaper,
    and then for the respawn."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver, _Worker

    driver = ElasticDriver.__new__(ElasticDriver)
    driver._lock = threading.RLock()
    driver._min_np = 2
    driver._start_timeout = 10
    driver._final_codes = []
    driver._reconcile_needed = threading.Event()
    driver._verbose = False
    driver._rendezvous = RendezvousServer()
    try:
        victim = _Worker("h:victim", "h", 0)
        survivor = _Worker("h:survivor", "h", 1)
        driver._workers = {w.worker_id: w for w in (victim, survivor)}
        client = RendezvousClient("127.0.0.1", driver._rendezvous.port)
        for w in (victim, survivor):
            client.register(w.worker_id, w.host, w.local_index, None)
        driver._cut_epoch([victim, survivor])
        assert driver._rendezvous.epoch == 1

        # The victim is dead and not reaped; the survivor asks for a
        # newer epoch than the one it consumed.
        client.register(survivor.worker_id, "h", 1, None, last_epoch=1)
        cut = threading.Thread(target=driver._cut_epoch,
                               args=([victim, survivor],),
                               kwargs={"asked_only": True})
        cut.start()
        time.sleep(0.5)
        assert driver._rendezvous.epoch == 1, "a rank for a dead worker"

        # The reaper runs (what _on_worker_exit does), a respawn
        # registers: the next cut is the survivor and the respawn, the
        # survivor rank 0.
        driver._final_codes.append(17)
        del driver._workers[victim.worker_id]
        driver._rendezvous.forget_worker(victim.worker_id)
        cut.join(timeout=10)
        assert not cut.is_alive()
        assert driver._rendezvous.epoch == 1       # one worker < min_np
        assert driver._reconcile_needed.is_set()
        respawn = _Worker("h:respawn", "h", 0)
        driver._workers[respawn.worker_id] = respawn
        client.register(respawn.worker_id, "h", 0, None)
        driver._cut_epoch([survivor, respawn])
        asg = client.poll_assignment(survivor.worker_id, timeout=5,
                                     min_epoch=2)
        assert (asg["epoch"], asg["rank"], asg["size"]) == (2, 0, 2)
    finally:
        driver._rendezvous.stop()
