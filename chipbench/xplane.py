"""The one reduction from a profiler trace (``*.xplane.pb``) to numbers,
on ``jax.profiler.ProfileData`` and nothing else.

What a TPU v5e trace looks like (read by hand, PR 24; PERF.md section 7):
one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one
event per executed HLO op (a ``while`` and the ops of its body are all
there, nested in time) and whose line ``XLA Modules`` holds one event per
executed program. Host threads are lines of the plane ``/host:CPU``;
``jax.profiler.TraceAnnotation`` spans land there under their own names.

Rules of the arithmetic:

- busy is the UNION of op intervals, so nesting and overlap count once;
- per-name time is SELF time: an event's duration minus the events
  nested inside it on the same line, so a loop and its body are not
  counted twice and the names sum to the union;
- the window is a whole number of steps: from the start of the anchor
  program's second execution to the start of its last, the anchor being
  the program that took most time. What the trace caught before and
  after, the possibly cut-off first execution included, is left out;
- exposed time of a class of ops is the part of its union during which
  no op outside the class runs on that chip.

``python chipbench/xplane.py --dump <file-or-dir>`` prints what a trace
holds: planes, lines, and the heaviest names — look before you match.
"""

import collections
import glob
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PREFIX, HOST_PLANE = "/device:TPU:", "/host:CPU"


def find(trace_dir):
    """The newest ``*.xplane.pb`` under a profiler output directory."""
    if os.path.isfile(trace_dir):
        return trace_dir
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find(path))


Event = collections.namedtuple("Event", "name start end stats")


def events_of(line, with_stats=False):
    out = []
    for e in line.events:
        stats = {k: v for k, v in e.stats} if with_stats else None
        out.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         stats))
    out.sort(key=lambda e: (e.start, -e.end))
    return out


def union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def subtract(a, b):
    """The part of union ``a`` not covered by union ``b`` (both merged)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """[(event, self_ns, encloses)]: duration minus the events nested
    inside it; ``encloses`` says that something was. ``events`` sorted
    by (start, -end), so a parent comes before its children. An event
    that starts inside another and ends after it is its sibling, not its
    child: each keeps its own duration."""
    out, stack = [], []   # stack of [event, self, encloses]
    for ev in events:
        while stack and (stack[-1][0].end <= ev.start
                         or stack[-1][0].end < ev.end):
            out.append(tuple(stack.pop()))
        if stack:   # nested in the top of the stack: take it off
            stack[-1][1] = max(stack[-1][1] - (ev.end - ev.start), 0)
            stack[-1][2] = True
        stack.append([ev, ev.end - ev.start, False])
    while stack:
        out.append(tuple(stack.pop()))
    return out


def step_window(modules):
    """(t0, t1, steps, anchor): a whole number of steps, anchored on the
    program that took most time: from the start of its SECOND execution
    in the trace to the start of its last. The first execution is left
    out because the trace may have begun in the middle of it, and its
    start is then the trace's and not the program's (seen on the v5e,
    PR 24: a first event 25 ms short). Fewer than three give no
    window."""
    by_name = collections.defaultdict(float)
    for m in modules:
        by_name[m.name] += m.end - m.start
    if not by_name:
        return None
    anchor = max(by_name, key=by_name.get)
    starts = [m.start for m in modules if m.name == anchor]
    if len(starts) < 3:
        return None
    return starts[1], starts[-1], len(starts) - 2, anchor


class Chip:
    """One device plane, reduced over a window of whole steps."""

    def __init__(self, plane):
        lines = {ln.name: ln for ln in plane.lines}
        self.name = plane.name
        self.ops = events_of(lines[OPS_LINE]) if OPS_LINE in lines else []
        self.modules = events_of(lines[MODULES_LINE]) \
            if MODULES_LINE in lines else []
        win = step_window(self.modules)
        if win is None and self.ops:   # no module line: all that ran
            win = (self.ops[0].start, max(e.end for e in self.ops), 0,
                   None)
        self.t0, self.t1, self.steps, self.anchor = win or (0, 0, 0, None)
        self.busy = union(clip([(e.start, e.end) for e in self.ops],
                               self.t0, self.t1))

    @property
    def window_ns(self):
        return self.t1 - self.t0

    @property
    def busy_ns(self):
        return total(self.busy)

    @property
    def idle_pct(self):
        """1 - busy / window, in percent; None without a window."""
        if self.window_ns <= 0:
            return None
        return 100.0 * (1.0 - self.busy_ns / self.window_ns)

    def _clipped(self, ev):
        return Event(ev.name, max(ev.start, self.t0), min(ev.end, self.t1),
                     ev.stats)

    def ops_in_window(self):
        return [self._clipped(e) for e in self.ops
                if e.end > self.t0 and e.start < self.t1]

    def self_ns_by(self, key):
        """{key(event): self ns} over the window; keys of None are
        dropped. The values sum to ``busy_ns`` when no key is dropped."""
        out = collections.defaultdict(float)
        for ev, ns, _ in self_times(self.ops_in_window()):
            k = key(ev)
            if k is not None:
                out[k] += ns
        return dict(out)

    def class_ns(self, member):
        """Union time of the ops for which ``member(event)`` is true."""
        return total(union((e.start, e.end) for e in self.ops_in_window()
                           if member(e)))

    def exposed_ns(self, member):
        """Time inside ops of the class during which no op outside the
        class runs on this chip. Containers (an op that encloses others,
        as a ``while`` does) are not "other work": only leaves count."""
        leaves = [ev for ev, _, encloses in
                  self_times(self.ops_in_window()) if not encloses]
        mine = union((e.start, e.end) for e in leaves if member(e))
        other = union((e.start, e.end) for e in leaves if not member(e))
        return total(subtract(mine, other))

    def module_ns(self, member):
        return sum(min(m.end, self.t1) - max(m.start, self.t0)
                   for m in self.modules if member(m)
                   and m.end > self.t0 and m.start < self.t1)

    def idle_gaps(self):
        """[(start, end)] of the window in which nothing ran."""
        return subtract([(self.t0, self.t1)], self.busy) \
            if self.t1 > self.t0 else []


def chips(profile):
    return [Chip(p) for p in profile.planes
            if p.name.startswith(DEVICE_PREFIX)
            and any(ln.name == OPS_LINE for ln in p.lines)]


def host_spans(profile, names):
    """Spans of the benchmark's own ``TraceAnnotation``s, from every
    host thread: [(name, start, end)] sorted by start."""
    out = []
    for p in profile.planes:
        if p.name != HOST_PLANE:
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name in names:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return sorted(out, key=lambda s: s[1])


def name_gaps(gaps, spans, top=10):
    """The longest idle gaps, each named by the host span open at its
    middle (the innermost, i.e. the latest started), else "none"."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid, name = (s + e) / 2, "none"
        for n, a, b in spans:
            if a <= mid < b:
                name = n
        out.append([name, (e - s) / 1e9])
    return out


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def opcode(ev):
    """The kind of op. On this chip an op's event is named by its whole
    HLO text, ``%fusion.350 = bf16[2,4096,4096]{...} fusion(...)``: the
    opcode is the first lower-case word that opens a parenthesis after
    the ``=`` (types hold none: ``T(8,128)`` and ``S(1)`` are upper
    case). A bare name gives its stem, ``fusion.2`` -> ``fusion``."""
    head, eq, rest = ev.name.partition(" = ")
    m = _OPCODE.search(rest) if eq else None
    if m:
        return m.group(1)
    return head.lstrip("%").split(".")[0]


def short_name(ev):
    """``fusion.350 bf16[2,4096,4096]``: the op's own name and the type
    it produces, without layouts and operands."""
    head, eq, rest = ev.name.partition(" = ")
    head = head.lstrip("%")
    if not eq:
        return head
    kind = opcode(ev)
    typ = rest.split(" " + kind + "(")[0]
    return (head + " " + re.sub(r"\{[^}]*\}", "", typ))[:96]


def is_mosaic_call(ev):
    """A Mosaic (pallas) kernel: a custom call whose target is
    ``tpu_custom_call``, whatever the kernel is named."""
    return opcode(ev) == "custom-call" and "tpu_custom_call" in ev.name


def is_all_reduce(ev):
    return opcode(ev).startswith("all-reduce")


def _dump(path, top=25):
    prof = load(path)
    for p in prof.planes:
        print(f"PLANE {p.name!r}")
        for ln in p.lines:
            evs = events_of(ln, with_stats=True)
            span = (max(e.end for e in evs) - evs[0].start) / 1e6 \
                if evs else 0
            print(f"  LINE {ln.name!r}: {len(evs)} events over "
                  f"{span:.3f} ms")
            agg = collections.defaultdict(lambda: [0, 0.0])
            for ev, ns, _ in self_times(evs):
                a = agg[ev.name]
                a[0] += 1
                a[1] += ns
            for name, (n, ns) in sorted(agg.items(),
                                        key=lambda kv: -kv[1][1])[:top]:
                print(f"      {ns / 1e6:12.3f} ms self  x{n:<6} {name[:200]}")
            if evs and p.name.startswith(DEVICE_PREFIX):
                ev = max(evs, key=lambda e: e.end - e.start)
                print(f"      stats of the longest ({ev.name}): "
                      f"{ev.stats}")
    for c in chips(prof):
        print(f"CHIP {c.name}: anchor {c.anchor!r}, {c.steps} steps, "
              f"window {c.window_ns / 1e6:.3f} ms, busy "
              f"{c.busy_ns / 1e6:.3f} ms")
        for k, ns in sorted(c.self_ns_by(opcode).items(),
                            key=lambda kv: -kv[1])[:top]:
            print(f"      {ns / 1e6:12.3f} ms  opcode {k}")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--dump":
        _dump(sys.argv[2])
    else:
        raise SystemExit(__doc__)
