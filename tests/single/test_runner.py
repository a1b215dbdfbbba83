"""Launcher unit + integration tests.

Reference analog: test/single/test_run.py (arg parsing, host parsing,
cmdline construction with mocks) plus a real local 2-rank launch as the
integration probe (SURVEY.md §4).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.runner import launch, util

# Part of the sub-5-minute CI lane (make test-quick).
pytestmark = pytest.mark.quick

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_parse_hosts():
    hosts = util.parse_hosts("a:2,b:4,c")
    assert [(h.hostname, h.slots) for h in hosts] == [("a", 2), ("b", 4),
                                                      ("c", 1)]


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hosts"
    f.write_text(textwrap.dedent("""\
        # comment
        node1 slots=4
        node2:2
        node3
    """))
    hosts = util.parse_hostfile(str(f))
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("node1", 4), ("node2", 2), ("node3", 1)]


def test_host_assignments():
    slots = util.get_host_assignments(util.parse_hosts("a:2,b:2"), 3)
    assert [(s.hostname, s.rank, s.local_rank, s.cross_rank)
            for s in slots] == [("a", 0, 0, 0), ("a", 1, 1, 0),
                                ("b", 2, 0, 1)]
    assert all(s.cross_size == 2 for s in slots)
    assert slots[2].local_size == 1

    with pytest.raises(ValueError):
        util.get_host_assignments(util.parse_hosts("a:1"), 2)


def test_parse_args_and_env():
    args = launch.parse_args([
        "-np", "2", "--fusion-threshold-mb", "32", "--cycle-time-ms", "5",
        "--timeline-filename", "/tmp/t.json", "--no-stall-check",
        "--log-level", "DEBUG", "python", "train.py"])
    env = launch.env_from_args(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "5.0"
    assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"
    assert env["HOROVOD_LOG_LEVEL"] == "DEBUG"
    assert args.command == ["python", "train.py"]


def test_config_file(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("fusion-threshold-mb: 8\nlog-level: INFO\n")
    args = launch.parse_args(["-np", "1", "--config-file", str(cfg),
                              "python", "x.py"])
    env = launch.env_from_args(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(8 * 1024 * 1024)
    assert env["HOROVOD_LOG_LEVEL"] == "INFO"


def test_ssh_wrap():
    slot = util.SlotInfo("remotehost", 1, 0, 1, 2, 1, 2)
    cmd = launch._ssh_wrap(slot, {"HOROVOD_RANK": "1"}, ["python", "t.py"],
                           2222, "/id_rsa")
    assert cmd[0] == "ssh"
    assert "-p" in cmd and "2222" in cmd
    assert "remotehost" in cmd
    assert "HOROVOD_RANK=1" in cmd[-1]


def test_horovodrun_end_to_end(tmp_path):
    """Real 2-rank launch through the CLI: each rank allreduces its rank."""
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""\
        import numpy as np
        from horovod_tpu.common.basics import HorovodBasics
        from horovod_tpu.common import eager_ops
        b = HorovodBasics(); b.init()
        h = eager_ops.allreduce_async(
            np.full(4, float(b.rank()), np.float32), "t")
        out = h.synchronize()
        assert out[0] == sum(range(b.size())), out
        print(f"RANK{b.rank()}-SUM{out[0]:.0f}")
        b.shutdown()
    """))
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert "RANK0-SUM1" in proc.stdout
    assert "RANK1-SUM1" in proc.stdout


def test_horovodrun_propagates_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys, os\n"
                      "sys.exit(3 if os.environ['HOROVOD_RANK']=='1' else 0)")
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 1
    assert "ranks failed" in proc.stderr


# ---- mpi_run / js_run cmdline construction (reference: test_run.py's
# mpirun cmdline asserts, fully mocked — no MPI needed) ----

def test_build_mpi_command_openmpi():
    from horovod_tpu.runner.mpi_run import MpiFlavor, build_mpi_command

    hosts = util.parse_hosts("h1:2,h2:2")
    env = {"HOROVOD_FUSION_THRESHOLD": "1", "PATH": "/bin", "HOME": "/root"}
    cmd = build_mpi_command(4, hosts, ["python", "train.py"], env,
                            flavor=MpiFlavor.OPENMPI, ssh_port=2222)
    assert cmd[0] == "mpirun"
    assert "-H" in cmd and cmd[cmd.index("-H") + 1] == "h1:2,h2:2"
    assert cmd[cmd.index("-np") + 1] == "4"
    assert ["--bind-to", "none"] == cmd[cmd.index("--bind-to"):
                                        cmd.index("--bind-to") + 2]
    # env forwarding: HOROVOD_* and PATH yes, HOME no
    xs = [cmd[i + 1] for i, c in enumerate(cmd) if c == "-x"]
    assert "HOROVOD_FUSION_THRESHOLD" in xs and "PATH" in xs
    assert "HOME" not in xs
    assert "plm_rsh_args" in cmd  # ssh port plumbed
    assert cmd[-2:] == ["python", "train.py"]


def test_build_mpi_command_mpich():
    from horovod_tpu.runner.mpi_run import MpiFlavor, build_mpi_command

    hosts = util.parse_hosts("h1:2")
    cmd = build_mpi_command(2, hosts, ["python", "t.py"],
                            {"HOROVOD_RANK": "0"}, flavor=MpiFlavor.MPICH)
    assert "-genvlist" in cmd and "-hosts" in cmd
    assert cmd[-2:] == ["python", "t.py"]


def test_detect_mpi_flavor():
    from horovod_tpu.runner.mpi_run import MpiFlavor, detect_mpi_flavor

    assert detect_mpi_flavor("mpirun (Open MPI) 4.1.4") == MpiFlavor.OPENMPI
    assert detect_mpi_flavor("HYDRA build details:") == MpiFlavor.MPICH
    assert detect_mpi_flavor("Intel(R) MPI Library") == MpiFlavor.INTEL
    assert detect_mpi_flavor("???") == MpiFlavor.UNKNOWN


def test_lsf_hosts_parsing():
    from horovod_tpu.runner.js_run import LSFUtils, build_js_command

    env = {"LSB_JOBID": "1", "LSB_MCPU_HOSTS": "batch 1 c1 4 c2 4"}
    assert LSFUtils.using_lsf(env)
    hosts = LSFUtils.get_compute_hosts(env)
    assert [(h.hostname, h.slots) for h in hosts] == [("c1", 4), ("c2", 4)]
    assert LSFUtils.get_num_processes(env) == 8
    # One resource set per host carrying all its ranks (multiple all-CPU
    # RSes on one host would be an infeasible jsrun geometry).
    cmd = build_js_command(2, 4, ["python", "t.py"])
    assert cmd[0] == "jsrun"
    assert cmd[cmd.index("--nrs") + 1] == "2"
    assert cmd[cmd.index("--tasks_per_rs") + 1] == "4"
    assert cmd[cmd.index("--rs_per_host") + 1] == "1"


def test_run_controller_choice():
    args = launch.parse_args(["-np", "2", "--mpi", "--", "python", "t.py"])
    assert launch.run_controller(args) == "mpi"
    args = launch.parse_args(["-np", "2", "--", "python", "t.py"])
    assert launch.run_controller(args) == "gloo"
    args = launch.parse_args(["-np", "2", "--js", "--", "python", "t.py"])
    assert launch.run_controller(args) == "js"
    with pytest.raises(ValueError):
        args = launch.parse_args(
            ["-np", "2", "--mpi", "--js", "--", "python", "t.py"])
        launch.run_controller(args)


# ---- driver/task NIC discovery (reference: test_run.py service tests;
# multi-host faked as threads on loopback, SURVEY.md §4) ----

def test_nic_discovery_roundtrip():
    from horovod_tpu.runner.task_service import (
        HorovodRunTaskService,
        discover_common_interfaces,
    )

    def spawn(driver):
        return [HorovodRunTaskService(i, driver.addresses, driver.key)
                for i in range(3)]

    common = discover_common_interfaces(3, spawn, timeout=30)
    assert set(common) == {0, 1, 2}
    # every host is reachable from the others via at least one address
    for idx, addrs in common.items():
        assert addrs, f"no common interface found for task {idx}"


def test_driver_rejects_bad_hmac():
    import socket

    from horovod_tpu.runner.driver_service import (
        HorovodRunDriverService,
        send_msg,
    )

    driver = HorovodRunDriverService(1)
    try:
        with socket.create_connection(driver.addresses, timeout=5) as s:
            send_msg(s, {"type": "register", "index": 0, "host": "x",
                         "addrs": []}, "wrong-key")
            f = s.makefile("rb")
            assert f.readline() == b""  # connection dropped, no ack
        assert driver._registered == {}
    finally:
        driver.shutdown()


def test_launcher_env_translation(monkeypatch):
    """Under mpirun/srun the rank layout arrives in OMPI_*/SLURM_* vars;
    init must translate them to HOROVOD_* (reference: MPIContext)."""
    from horovod_tpu.common.basics import HorovodBasics

    for k in ("HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
              "HOROVOD_LOCAL_SIZE"):
        # setenv first: delenv of an absent name records nothing, and
        # what the translation then writes would outlive the test.
        monkeypatch.setenv(k, "")
        monkeypatch.delenv(k)
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "8")
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_RANK", "1")
    monkeypatch.setenv("SLURM_TASKS_PER_NODE", "4(x2)")
    HorovodBasics._translate_launcher_env()
    assert os.environ["HOROVOD_RANK"] == "3"
    assert os.environ["HOROVOD_SIZE"] == "8"
    assert os.environ["HOROVOD_LOCAL_RANK"] == "1"
    assert os.environ["HOROVOD_LOCAL_SIZE"] == "4"  # '(x2)' stripped
    # Explicit HOROVOD_* wins over launcher vars.
    monkeypatch.setenv("HOROVOD_RANK", "0")
    HorovodBasics._translate_launcher_env()
    assert os.environ["HOROVOD_RANK"] == "0"


def test_launcher_env_written_by_a_test_ends_with_it(request,
                                                     monkeypatch):
    """The translation writes ``os.environ`` itself; tests/conftest.py
    takes what a test wrote under HOROVOD_/OMPI_/SLURM_ away after it,
    so the next test of this worker sees HOROVOD_RANK absent."""
    from horovod_tpu.common.basics import HorovodBasics
    from tests.conftest import launcher_env_restored

    assert "_launcher_env_restored" in request.fixturenames  # autouse
    for k in ("HOROVOD_RANK", "HOROVOD_SIZE"):
        monkeypatch.setenv(k, "")
        monkeypatch.delenv(k)
    monkeypatch.setenv("HOROVOD_CYCLE_TIME", "5")
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "8")
    with launcher_env_restored():  # one test's body
        HorovodBasics._translate_launcher_env()
        os.environ["HOROVOD_CYCLE_TIME"] = "1"
        os.environ["SLURM_WRITTEN_BY_A_TEST"] = "3"
        assert os.environ["HOROVOD_RANK"] == "3"
    assert not {"HOROVOD_RANK", "HOROVOD_SIZE",
                "SLURM_WRITTEN_BY_A_TEST"} & set(os.environ)
    assert os.environ["HOROVOD_CYCLE_TIME"] == "5"
    assert os.environ["OMPI_COMM_WORLD_RANK"] == "3"


def _interactive_fn(scale):
    """Module-level (picklable) fn for horovod_tpu.runner.run."""
    import numpy as np

    import horovod_tpu.jax as hvd

    hvd.init()
    try:
        out = hvd.allreduce(np.full(3, float(hvd.rank() + 1)), op=hvd.Sum)
        return float(np.asarray(out)[0]) * scale
    finally:
        hvd.shutdown()


def test_interactive_run():
    """Reference analog: test_interactiverun.py — horovod.run() launches
    fn on N local ranks, initializes each, returns results by rank."""
    import os

    from horovod_tpu import runner

    before = os.environ.get("HOROVOD_RANK")
    env = {"JAX_PLATFORMS": "cpu",
           "HOROVOD_XLA_DATA_PLANE": "0"}
    # Generous per-rank timeout: spawned workers import TF/JAX on a
    # single shared core and can take minutes when the machine is loaded.
    results = runner.run(_interactive_fn, args=(10.0,), np=2, env=env,
                         timeout=300)
    assert results == [30.0, 30.0]  # sum(1..2) * 10 on both ranks
    # run() must not mutate the parent environment (other tests may have
    # set HOROVOD_RANK before us; assert it is unchanged, not absent).
    assert os.environ.get("HOROVOD_RANK") == before


def test_tpu_pod_slot_env_binding():
    """--tpu-pod chip binding: four ranks sharing one 2x2 host get ONE
    process grid spanning the host — one chip per process, process
    bounds 2,2,1, every rank's address, a per-rank port and task id —
    not four standalone one-chip topologies (where no collective can
    cross chips); a user-exported grid wins; unknown layouts refuse;
    non-tpu-pod launches never set binding vars."""
    from unittest import mock

    import pytest

    from horovod_tpu.runner.launch import _slot_env
    from horovod_tpu.runner.util import SlotInfo

    ports = [8476, 8477, 8478, 8479]
    envs = []
    with mock.patch.dict(os.environ, clear=False):
        os.environ.pop("TPU_PROCESS_BOUNDS", None)
        for r in range(4):
            slot = SlotInfo(hostname="localhost", rank=r, local_rank=r,
                            cross_rank=0, size=4, local_size=4,
                            cross_size=1)
            envs.append(_slot_env(slot, "127.0.0.1", 29500, ports))
    for r, env in enumerate(envs):
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert env["TPU_PROCESS_ADDRESSES"] == ",".join(
            f"localhost:{p}" for p in ports)
        assert env["TPU_PROCESS_PORT"] == str(ports[r])
        assert env["TPU_VISIBLE_CHIPS"] == str(r)
        assert env["CLOUD_TPU_TASK_ID"] == str(r)
        assert env["HOROVOD_RANK"] == str(r)
    # the grid is identical on every rank; only port/chip/task differ
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1

    slot = SlotInfo(hostname="localhost", rank=1, local_rank=1,
                    cross_rank=0, size=2, local_size=2, cross_size=1)
    with mock.patch.dict(os.environ, clear=False):
        os.environ.pop("TPU_PROCESS_BOUNDS", None)
        with pytest.raises(SystemExit, match="no known process grid"):
            _slot_env(slot, "127.0.0.1", 29500, ports[:2])
    with mock.patch.dict(os.environ, {"TPU_PROCESS_BOUNDS": "2,1,1"}):
        env = _slot_env(slot, "127.0.0.1", 29500, ports[:2])
        assert env["TPU_PROCESS_BOUNDS"] == "2,1,1"
        assert env["TPU_VISIBLE_CHIPS"] == "1"

    # non-tpu-pod launches never set binding vars
    env = _slot_env(slot, "127.0.0.1", 29500)
    assert not [k for k in env if k.startswith("TPU_")]


def test_tpu_pod_counts_chips_without_jax(tmp_path):
    """The launcher counts chips on the PCI bus (vendor/device ids) that
    also have a device node — so it never opens the chips its ranks are
    about to claim, and a sandbox that lists four chips on the bus but
    hands over one counts one."""
    from horovod_tpu.runner.util import local_tpu_chips

    bus, dev = tmp_path / "pci", tmp_path / "dev"
    for i, (vendor, device) in enumerate(
            [("0x1ae0", "0x0063"), ("0x1ae0", "0x0063"),
             ("0x1ae0", "0x9999"), ("0x8086", "0x0063")]):
        d = bus / f"0000:00:0{i}.0"
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()          # the control node: no chip
    assert local_tpu_chips(str(bus), str(dev)) == 0   # nothing to open
    (dev / "vfio" / "2").touch()
    assert local_tpu_chips(str(bus), str(dev)) == 1   # one handed over
    (dev / "vfio" / "3").touch()
    (dev / "vfio" / "4").touch()
    assert local_tpu_chips(str(bus), str(dev)) == 2   # two on the bus
    assert local_tpu_chips(str(tmp_path / "absent"), str(dev)) == 0


def test_check_build_reports_capabilities(capsys):
    """horovodrun --check-build (reference parity): frameworks, planes,
    and the TF native op capability print truthfully."""
    from horovod_tpu.runner.launch import _print_check_build

    _print_check_build()
    out = capsys.readouterr().out
    assert "Available Frameworks" in out
    assert "[X] JAX" in out
    assert "[X] TCP (gloo-style rendezvous)" in out
    assert "[X] host ring (TCP)" in out
    assert "[X] xla_ici device plane (TPU/ICI)" in out
    # this image ships TF headers, so the native op row must be on
    assert "[X] TF native ops (in-jit XLA collectives)" in out
    assert "[ ] NCCL" in out
