"""hvdlint library API (the CLI lives in ``analysis/lint.py``).

::

    from horovod_tpu import analysis
    diags = analysis.lint(step_fn, (carry, batch), mesh=mesh)
    assert not analysis.errors(diags)

The analyzer traces with ``jax.make_jaxpr(fn, axis_env=...)`` so
collective axis names bind WITHOUT shard_map or real devices.
"""

import re

import jax

from horovod_tpu.analysis import checks
from horovod_tpu.analysis import diagnostics as D
from horovod_tpu.analysis.extract import extract

# jax 0.9.0: "Found an unbound axis name: rank. To fix this, ..." — a
# full stop may stand inside a name, never at its end.
_UNBOUND_RE = re.compile(r"unbound axis name:\s*([\w/-]+(?:\.[\w/-]+)*)")

#: how many distinct undeclared axis names one trace may reveal before
#: we give up retrying (each retry binds one more name)
_MAX_UNDECLARED = 8


def _axis_env_from_mesh(mesh):
    if mesh is None:
        return []
    return [(str(name), int(size))
            for name, size in dict(mesh.shape).items()]


def _trace(fn, args, kwargs, axis_env):
    """Trace ``fn`` to a ClosedJaxpr, auto-binding undeclared axis
    names (size 1) so C2 can report them with a real location instead
    of dying on jax's trace-time NameError. Returns
    ``(closed_jaxpr, undeclared_names, trace_error)``."""
    env = list(axis_env)
    undeclared = []
    for _ in range(_MAX_UNDECLARED + 1):
        try:
            closed = jax.make_jaxpr(
                lambda *a: fn(*a, **kwargs) if kwargs else fn(*a),
                axis_env=env)(*args)
            return closed, undeclared, None
        except NameError as e:
            m = _UNBOUND_RE.search(str(e))
            if not m:
                return None, undeclared, e
            name = m.group(1)
            if name in (n for n, _ in env):
                return None, undeclared, e
            undeclared.append(name)
            env.append((name, 1))
    return None, undeclared, None


def lint(fn, args=(), kwargs=None, *, mesh=None, axis_env=None,
         donate_argnums=(), expect_collectives=None, allow=()):
    """Statically analyze one program for SPMD collective-consistency.

    ``fn`` is any function the repo jits (the train step, a pipeline
    engine's inner program, an optimizer apply...); ``args`` are real
    arrays or ``jax.ShapeDtypeStruct`` placeholders. ``mesh`` declares
    the valid collective axes (or pass ``axis_env`` as
    ``[(name, size), ...]`` to lint a manual per-device program such as
    a pipeline inner without building a mesh). ``donate_argnums``
    applies check C4 to ``fn``'s own top-level arguments; donations
    inside jitted sub-programs are discovered automatically from their
    pjit equations. ``expect_collectives`` (from
    ``parallel.pipeline.predicted_collectives``) enables check C5.
    ``allow`` suppresses diagnostics by id (``"C3"``) or id:path.

    Returns a list of :class:`~horovod_tpu.analysis.diagnostics.Diagnostic`.
    """
    kwargs = dict(kwargs or {})
    env = list(axis_env) if axis_env is not None \
        else _axis_env_from_mesh(mesh)
    declared = [n for n, _ in env]

    closed, undeclared, err = _trace(fn, args, kwargs, env)
    if closed is None:
        diags = [D.make(
            "C2", "<trace>",
            f"program could not be traced: {err}",
            hint="collectives reference axis names the mesh does not "
                 "declare")]
        return D.filter_allowed(diags, allow)

    ex = extract(closed)
    if donate_argnums:
        _add_top_level_donation(ex, closed, fn, args, donate_argnums)

    ctx = {
        # When the caller declared no axes at all, C2 has no ground
        # truth — skip it rather than flagging everything. Auto-bound
        # undeclared names stay OUT of the declared set so the
        # collectives that referenced them are flagged with their
        # real location.
        "mesh_axes": declared if (declared or undeclared) else None,
        "expect_collectives": expect_collectives,
    }
    diags = checks.run_all(ex, ctx)
    return D.filter_allowed(diags, allow)


def _add_top_level_donation(ex, closed, fn, args, donate_argnums):
    """Model explicit donate_argnums on a non-jitted ``fn`` as a
    donation site over the top-level jaxpr (C4 handles the rest)."""
    from horovod_tpu.analysis.extract import DonationSite

    flags = []
    for i, a in enumerate(args):
        n = len(jax.tree.leaves(a))
        flags.extend([i in set(donate_argnums)] * n)
    jaxpr = closed.jaxpr
    if len(flags) != len(jaxpr.invars):
        # kwargs or non-pytree args shifted the flat arity; refuse to
        # guess rather than misattribute donation.
        return
    ex.donation_sites.append(DonationSite(
        name=getattr(fn, "__name__", "<fn>"),
        path="<top>", source="", jaxpr=closed, donated=tuple(flags)))


def errors(diags):
    """Error-severity subset (what CI gates on)."""
    return D.errors(diags)
