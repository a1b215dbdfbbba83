"""Launcher utilities: host parsing, rank layout, networking.

Reference analog: ``horovod/runner/common/util/hosts.py`` (parse_hosts,
get_host_assignments) and ``network.py``.
"""

import dataclasses
import glob
import os
import socket


@dataclasses.dataclass
class HostInfo:
    hostname: str
    slots: int


@dataclasses.dataclass
class SlotInfo:
    hostname: str
    rank: int
    local_rank: int
    cross_rank: int
    size: int
    local_size: int
    cross_size: int


def parse_hosts(hosts_str):
    """'host1:2,host2:4' -> [HostInfo]. Bare 'host' means 1 slot."""
    hosts = []
    for part in hosts_str.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            name, slots = part.rsplit(":", 1)
            hosts.append(HostInfo(name, int(slots)))
        else:
            hosts.append(HostInfo(part, 1))
    return hosts


def parse_hostfile(path):
    """One 'hostname slots=N' (or 'hostname:N' or bare) per line; # comments."""
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            if "slots=" in line:
                name, _, slots = line.partition("slots=")
                hosts.append(HostInfo(name.strip(), int(slots)))
            elif ":" in line:
                name, slots = line.rsplit(":", 1)
                hosts.append(HostInfo(name.strip(), int(slots)))
            else:
                hosts.append(HostInfo(line, 1))
    return hosts


def get_host_assignments(hosts, np):
    """Fill ranks across hosts in order; error if slots < np.

    Mirrors the reference's round-robin-by-host-order placement
    (horovod/runner/common/util/hosts.py get_host_assignments).
    """
    total = sum(h.slots for h in hosts)
    if total < np:
        raise ValueError(
            f"requested -np {np} but hosts only provide {total} slots")
    slots = []
    rank = 0
    used_hosts = []
    for cross_rank, h in enumerate(hosts):
        if rank >= np:
            break
        n_here = min(h.slots, np - rank)
        used_hosts.append((h, n_here))
        for local_rank in range(n_here):
            slots.append(SlotInfo(h.hostname, rank, local_rank, cross_rank,
                                  np, n_here, 0))
            rank += 1
    cross_size = len(used_hosts)
    for s in slots:
        s.cross_size = cross_size
    return slots


def free_port(addr="0.0.0.0"):
    s = socket.socket()
    s.bind((addr, 0))
    port = s.getsockname()[1]
    s.close()
    return port


_LOCAL_NAMES = {"localhost", "127.0.0.1", "::1"}


def is_local_host(hostname):
    if hostname in _LOCAL_NAMES:
        return True
    try:
        local = {socket.gethostname(), socket.getfqdn()}
    except OSError:
        local = set()
    return hostname in local


def resolvable_addr_for(hosts):
    """Controller address the workers should dial: loopback when all hosts
    are local, else this host's primary address."""
    if all(is_local_host(h.hostname) for h in hosts):
        return "127.0.0.1"
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("10.255.255.255", 1))
        return s.getsockname()[0]
    except OSError:
        return socket.gethostbyname(socket.gethostname())
    finally:
        s.close()


# Google's PCI vendor id and the device ids of its TPU chips (v3, v4,
# v5p, v5e, v6e, 7x) — the table jax's own start-up probe uses.
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063",
                    "0x006f", "0x0076"}


def local_tpu_chips(sysfs="/sys/bus/pci/devices", dev="/dev"):
    """Number of TPU chips this host can open: chips on the PCI bus that
    also have a device node (``/dev/accel*`` up to v4, one
    ``/dev/vfio/<group>`` per chip from v5e on). A sandbox may list a
    whole host's chips on the bus and hand over only some of them.

    Deliberately jax-free: the ``--tpu-pod`` launcher counts chips with
    it before spawning one rank per chip, and a chip belongs to one
    process at a time — a launcher that asked ``jax.local_devices()``
    would hold every chip its ranks are about to open.
    """
    on_bus = 0
    for vendor_path in glob.glob(os.path.join(sysfs, "*", "vendor")):
        try:
            with open(vendor_path) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(os.path.dirname(vendor_path),
                                   "device")) as f:
                on_bus += f.read().strip() in _TPU_PCI_DEVICES
        except OSError:
            continue
    nodes = len(glob.glob(os.path.join(dev, "accel[0-9]*"))) \
        + len(glob.glob(os.path.join(dev, "vfio", "[0-9]*")))
    return min(on_bus, nodes)
