"""What the program itself writes into the profiler's trace
(docs/metrics.md "Program spans" and "Device scopes"): host spans, one
per layer boundary of the host's path, and device scopes, one per layer
boundary of the compiled programs; never one per tensor. Beside them,
what the program keeps about its own start-up whether or not a trace is
taken (docs/metrics.md "Set-up: the compile log and the start-up
marks"): the marks, one clock read each, once a process, and the count
of steps begun and returned. A leaf module: it imports ``jax`` and of
this package only the moment its import ended, so the lowest layers can
open a span or a scope without pulling in ``horovod_tpu.telemetry``.

A host span is an interval on the profiler's clock. A device scope is
metadata: ``jax.named_scope`` puts its name into the name stack of every
operation traced inside it, the name stack rides each instruction of the
compiled program as ``metadata={op_name=...}``, and a device trace names
each event by its instruction. :func:`scope_table` reads a compiled
program's text into ``{instruction: (scope, phase, mixed)}``; the
programs this package jits file themselves (:func:`register_program`) so
that :func:`scope_tables` can hand out the table of each by module name.
"""

import collections
import os
import re
import time

import jax
from jax.profiler import TraceAnnotation

from horovod_tpu import _imported_at

SPANS = frozenset({"hvd.enqueue", "hvd.device_exec", "hvd.wait",
                   "hvd.spmd.step"})

# Where each opens and what it holds: docs/metrics.md "Device scopes".
SCOPES = frozenset({
    "hvd.embed", "hvd.norm", "hvd.attn.proj", "hvd.attn.rope",
    "hvd.attn.core", "hvd.conv.proj", "hvd.conv.chain", "hvd.gdn.proj",
    "hvd.gdn.chain", "hvd.gdn.core", "hvd.ssm.proj", "hvd.ssm.chain",
    "hvd.ssm.core", "hvd.ssd.proj", "hvd.ssd.chain", "hvd.ssd.core",
    "hvd.sparse.select", "hvd.sparse.core", "hvd.lightning.chain",
    "hvd.lightning.core", "hvd.mla.proj", "hvd.mla.core", "hvd.hc.mix",
    "hvd.ffn", "hvd.moe.route", "hvd.moe.dispatch", "hvd.moe.experts",
    "hvd.moe.combine", "hvd.moe.latent", "hvd.mtp", "hvd.loop", "hvd.exit",
    "hvd.head", "hvd.loss", "hvd.apply",
    "hvd.allreduce", "hvd.cnn.stem", "hvd.cnn.stage1", "hvd.cnn.stage2",
    "hvd.cnn.stage3", "hvd.cnn.stage4", "hvd.cnn.head"})

PHASES = ("forward", "recomputed", "backward")

# Which line stamps each: docs/metrics.md "Set-up: the compile log and
# the start-up marks".
MARKS = frozenset({
    "hvd.imported", "hvd.cache.enabled", "hvd.init", "hvd.init.core",
    "hvd.init.plane", "hvd.step.first", "hvd.step.first_dispatched"})


def span(name, **carries):
    """A ``jax.profiler.TraceAnnotation`` under a name of :data:`SPANS`.

    The span lands on the ``/host:CPU`` plane of the same xplane file
    as the device ops, on the profiler's clock, on the thread that
    opened it. With no trace being taken it records nothing: it costs
    the name check and the profiler's flag test. ``carries`` become the
    event's stats; a value that is not free to compute is attached
    under ``if s.is_enabled(): s.set_metadata(...)`` instead.
    """
    if name not in SPANS:
        raise ValueError(f"{name!r} is not a program span: {sorted(SPANS)}")
    return TraceAnnotation(name, **carries)


def scope(name):
    """A ``jax.named_scope`` under a name of :data:`SCOPES`, opened in
    the function that does the work. It exists while a program is
    TRACED: the compiled program carries it as metadata and runs not one
    instruction more or fewer for it, so there is nothing to switch
    off. Innermost wins where scopes nest (an RMSNorm inside the
    projections reads ``hvd.norm``)."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a device scope: "
                         f"{sorted(SCOPES)}")
    return jax.named_scope(name)


# ---------------------------------------------------------------------
# Start-up marks and the step count


def _process_began():
    """``time.monotonic()`` at the moment this process began, by the
    kernel's account: its start in clock ticks since boot
    (``/proc/self/stat``, field 22) against the seconds since boot
    (``/proc/uptime``), both to 10 ms. ``None`` where there is no
    ``/proc``."""
    try:
        with open("/proc/self/stat") as f:
            # (the command, field 2, may hold spaces and parentheses)
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.monotonic() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None


_BEGAN = _process_began()
_marks = {}
_begun = _returned = 0   # steps; written on the user's thread alone


def since_start(now=None):
    """Seconds since the process began (``None`` where there is no
    ``/proc``): the clock of the marks and of the compile log's ``t``."""
    if _BEGAN is None:
        return None
    return (time.monotonic() if now is None else now) - _BEGAN


def mark(name):
    """Stamp the start-up mark ``name`` of :data:`MARKS` with
    :func:`since_start`, the first time it is reached and never again: a
    clock read and a dict store once a process, the same with a trace
    running."""
    if name not in MARKS:
        raise ValueError(f"{name!r} is not a start-up mark: {sorted(MARKS)}")
    if name not in _marks:
        _marks[name] = since_start()


def marks():
    """``{name: seconds since the process began}`` of the marks reached
    so far, in the order they were reached."""
    return dict(_marks)


def step_begins():
    """The dispatch of a step begins (``_spanned``,
    ``allreduce_gradients``): one integer add, no lock."""
    global _begun
    if not _begun:
        mark("hvd.step.first")
    _begun += 1


def step_returns():
    """The dispatch of that step has returned."""
    global _returned
    _returned += 1
    if _returned == 1:
        mark("hvd.step.first_dispatched")


def steps_begun():
    return _begun


def steps_returned():
    return _returned


_marks["hvd.imported"] = since_start(_imported_at)


# ---------------------------------------------------------------------
# A compiled program's text -> {instruction: Scoped}

Scoped = collections.namedtuple("Scoped", "scope phase mixed")
Scoped.__doc__ = """One instruction of a compiled program: the innermost
name of SCOPES in its name stack (None: it carries none), its phase, and
for a fusion whether the instructions fused into it carry more than one
scope (an RMSNorm folded into a matmul's prologue)."""

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s+(ROOT )?%?([\w.\-]+) = (.*?)([a-z][a-z0-9\-]*)\((.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def read_name_stack(stack):
    """A name stack (``jit(f)/transpose(jvp(hvd.norm))/checkpoint/
    rematted_computation/hvd.ffn/mul``) -> (scope, phase): the LAST name
    of :data:`SCOPES` in it, which is the innermost (a transform wraps
    what came before it), else None; ``recomputed`` where
    ``jax.checkpoint`` re-ran the forward (``rematted_computation``),
    ``backward`` under a ``transpose(``, else ``forward``."""
    found = [s for s in re.split(r"[/()]", stack) if s in SCOPES]
    if "rematted_computation" in stack:
        phase = "recomputed"
    elif "transpose(" in stack:
        phase = "backward"
    else:
        phase = "forward"
    return (found[-1] if found else None), phase


_MATMULS = ("dot", "convolution")


def _logical_lines(text):
    """The text's lines, an instruction that is printed over several (a
    Mosaic call's ``kernel_metadata={`` opens a line of its own) joined
    into one."""
    out = []
    for line in text.splitlines():
        if (out and out[-1].startswith("  ") and line not in ("", "}")
                and not line.startswith(("  ", "%", "ENTRY "))):
            out[-1] += line
        else:
            out.append(line)
    return out


def scope_table(hlo_text):
    """``compiled.as_text()`` -> ``{instruction name: Scoped}`` for
    every instruction of every computation of the module, the bodies of
    ``while``s and the branches of ``conditional``s included: whatever a
    device trace can show is in it, under the name the trace shows.

    The metadata read is the whole name stack in ``op_name``: what
    :func:`horovod_tpu.utils.compile_cache.enable_compile_cache` sets up,
    and jax's default. (With ``jax_include_full_tracebacks_in_locations``
    off ``op_name`` is the bare primitive and every row reads None.)

    A fusion is read by what it is built round: the ``dot`` or
    ``convolution`` it holds, whatever the compiler folded in before
    and behind it (read by its ROOT, the backward of an RMSNorm that
    rides a matmul's epilogue would take the matmul's time for the
    norm: 47 ms a step of the LFM2 cell, PERF.md section 6, PR 36). A
    fusion that holds none takes scope and phase from the root of its
    fused computation, or where the root carries none (a tuple, a
    bitcast, a residual add) from the nearest instruction the root is
    computed from that does, then from the fusion instruction's own
    metadata; a fusion inside a fusion is resolved first. ``mixed``
    says that the fused instructions carry more than one scope. An
    instruction outside every scope reads ``(None, phase, False)``; one
    with no metadata at all ``(None, "forward", False)``.
    """
    own, opcodes, fusions, computations = {}, {}, {}, {}
    members = None
    for line in _logical_lines(hlo_text):
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                members = computations.setdefault(c.group(1), [])
            continue
        root, name, _, opcodes[name], rest = m.groups()
        stack = _OP_NAME.search(rest)
        own[name] = read_name_stack(stack.group(1)) if stack else None
        if members is not None:
            operands = _OPERAND.findall(rest.split("), ", 1)[0])
            members.append((name, bool(root), operands))
        if opcodes[name] == "fusion":
            c = _CALLS.search(rest)
            if c:
                fusions[name] = c.group(1)

    resolved = {}

    def resolve(name):
        """-> ((scope, phase) or None, its scopes, holds a matmul)."""
        if name in resolved:
            return resolved[name]
        found = own.get(name)
        if name not in fusions:
            scopes = {found[0]} if found and found[0] else set()
            resolved[name] = (found, scopes, opcodes[name] in _MATMULS)
            return resolved[name]
        resolved[name] = (found, set(), False)   # (no cycle in HLO)
        fused = computations.get(fusions[name], [])
        inner = {n: resolve(n) for n, _, _ in fused}
        scopes = set().union(*(s for _, s, _ in inner.values()))
        matmuls = [f for f, _, matmul in inner.values()
                   if matmul and f and f[0]]
        if matmuls:
            resolved[name] = (matmuls[0], scopes, True)
            return resolved[name]
        # breadth first from the root towards what it is computed from
        operands_of = {n: ops for n, _, ops in fused}
        queue = [n for n, is_root, _ in fused if is_root]
        seen, near = set(queue), None
        while queue and near is None:
            n = queue.pop(0)
            if inner[n][0] and inner[n][0][0]:
                near = inner[n][0]
            for o in operands_of[n]:
                if o in operands_of and o not in seen:
                    seen.add(o)
                    queue.append(o)
        roots = [inner[n][0] for n, is_root, _ in fused
                 if is_root and inner[n][0]]
        near = near or found or (roots[0] if roots else None)
        resolved[name] = (near, scopes,
                          any(m for _, _, m in inner.values()))
        return resolved[name]

    table = {}
    for name in own:
        found, scopes, _ = resolve(name)
        table[name] = Scoped(*(found or (None, "forward")),
                             name in fusions and len(scopes) > 1)
    return table


# ---------------------------------------------------------------------
# The programs this package jits, by module name

_PROGRAMS = {}   # (function name, signature) -> (jitted, abstract args)


def _abstract(x):
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    # An uncommitted array lowers with no sharding on its argument, a
    # committed one with it: the filed signature has to lower to the
    # module that ran, or the compile cache knows nothing of it.
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def register_program(jitted, *args):
    """File a jitted program under the abstract signature it is being
    called with (shapes, dtypes and shardings; no buffer is kept), so
    that :func:`scope_tables` can lower it again. Called once, at a
    program's first call, by the code that owns the program."""
    abstract = jax.tree.map(_abstract, args)
    leaves, treedef = jax.tree.flatten(abstract)
    signature = (treedef, tuple(
        (x.shape, str(x.dtype), x.sharding)
        if isinstance(x, jax.ShapeDtypeStruct) else x for x in leaves))
    _PROGRAMS[getattr(jitted, "__name__", repr(jitted)), signature] = (
        jitted, abstract)


def files_itself(programs, attr, jitted):
    """Put ``jitted`` at ``programs.<attr>`` so that its FIRST call
    through that attribute files it (:func:`register_program`) and puts
    the jitted function itself in the attribute's place: from the second
    call on the caller reaches the program as directly as if nothing had
    been filed. A call with tracers (the caller is itself being
    traced) is no call of the program and files nothing."""
    def first(*args):
        if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(args)):
            return jitted(*args)   # inlined into an outer trace: no call
        setattr(programs, attr, jitted)
        register_program(jitted, *args)
        return jitted(*args)

    setattr(programs, attr, first)


def program_texts():
    """``[(module name, compiled text)]`` of every program filed so far:
    each is lowered for its filed signature and compiled, which the jit
    cache or the persistent compile cache answers where the program has
    run (no weights are allocated, nothing runs). Nothing is done before
    this is asked for."""
    return [named(jitted.lower(*abstract).compile().as_text())
            for jitted, abstract in list(_PROGRAMS.values())]


def named(text):
    """A compiled program's text -> (its module's name, the text)."""
    return re.match(r"HloModule ([\w.\-]+)", text).group(1), text


def merge_tables(tables):
    """One table for programs that share a module name (the device plane
    compiles one ``jit_hvd_allreduce`` a fusion group): the union of
    theirs; an instruction name that two of them scope differently
    reads ``(None, "forward", False)``."""
    out = {}
    for table in tables:
        for name, scoped in table.items():
            if out.setdefault(name, scoped) != scoped:
                out[name] = Scoped(None, "forward", False)
    return out


def tables_of(texts):
    """``[(module name, text)]`` -> ``{module name: scope table}``."""
    by_name = collections.defaultdict(list)
    for name, text in texts:
        by_name[name].append(scope_table(text))
    return {name: merge_tables(tables) for name, tables in by_name.items()}


def scope_tables():
    """``{module name: scope table}`` of the programs filed so far
    (``jit_hvd_grad``, ``jit_hvd_apply``, ``jit_hvd_allreduce``...): the
    name a device trace gives a program on its module line, less the
    ``(id)`` behind it."""
    return tables_of(program_texts())
