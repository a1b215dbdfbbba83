"""Jaxpr walking: turn any traced program into a collective signature.

The walker recursively descends every sub-jaxpr jax can produce —
``pjit`` bodies, ``scan``/``while`` loops, ``cond``/``switch`` branches,
``remat``/``checkpoint`` wrappers, custom-vjp calls — and records every
cross-device collective as a :class:`Collective` in program order,
preserving control-flow structure (:class:`Loop`, :class:`Branches`)
so the checks can reason per path. Alongside, it tracks:

- **rank taint**: which values derive (transitively) from
  ``lax.axis_index`` — a branch predicate tainted this way is
  device-varying, so differing branch signatures are a GUARANTEED
  cross-rank divergence, not just a possible one;
- **width provenance**: whether a reduction's operand was upcast from a
  sub-fp32 dtype, and whether its result is immediately cast back down
  (the deliberate f32-accumulate roundtrip) — check C3's raw material;
- **donation sites**: every ``pjit`` equation carrying donated invars,
  with its body jaxpr — check C4's raw material;
- **compute/collective profile**: a flattened program-order event list
  interleaving flop mass with collective issue points, so check C7 can
  tell a schedule that hides reduce-scatter wire time under remaining
  backward compute from one that bunches every scatter after the last
  flop — check C7's raw material.

Nothing here needs ``jax.shard_map`` or devices: programs are traced by
the caller with ``jax.make_jaxpr(fn, axis_env=...)``, which binds
collective axis names by itself.

A ``jax.jit`` call inside a program is one equation whose params carry
``donated_invars`` and the body ``jaxpr`` (:func:`_is_jit`); its
primitive's NAME is jax's to change (``pjit`` once, ``jit`` in 0.9.0),
so nothing here matches it. Paths label it ``pjit:<name>`` whatever jax
calls it: allow-lists are written against that label.
"""

import dataclasses

#: collective primitive name -> reduce op it applies (None = pure data
#: movement). ``axis_index`` is deliberately absent: it is local.
COLLECTIVE_PRIMS = {
    "psum": "sum",
    "pmax": "max",
    "pmin": "min",
    "psum_scatter": "sum",
    "reduce_scatter": "sum",
    "ppermute": None,
    "pbroadcast": None,
    "all_gather": None,
    "all_to_all": None,
    "pgather": None,
}

#: dtypes whose fp32 promotion before a reduction doubles wire bytes
_NARROW = ("bfloat16", "float16")


@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective equation in the traced program."""

    prim: str              # primitive name, e.g. "psum"
    axes: tuple            # axis names it runs over, in order
    dtype: str             # operand dtype(s), comma-joined if mixed
    nelems: int            # total elements across operands
    reduce_op: str         # "sum"/"max"/... or "" for data movement
    path: str              # structural path, e.g. "pjit:f/scan"
    source: str            # user file:line (best effort)
    upcast_from: str = ""  # operand was convert_element_type'd from this
    roundtrip: bool = False  # every consumer casts straight back down

    @property
    def key(self):
        """Identity for sequence comparison: what must match across
        ranks for the collective to rendezvous."""
        return (self.prim, self.axes, self.dtype, self.nelems,
                self.reduce_op)


@dataclasses.dataclass(frozen=True)
class Loop:
    """A scan/while body; its signature repeats ``length`` times
    (``None`` when the trip count is not static — while loops).

    ``trip_rank_dependent`` marks a while loop whose cond output is
    (transitively) derived from ``lax.axis_index``: ranks run
    DIFFERENT iteration counts, so any collective in the body
    rendezvouses across mismatched iterations (C8). Scans always have
    a static trip count and stay False."""

    body: tuple            # tuple of signature nodes
    length: "int | None"
    path: str
    source: str
    trip_rank_dependent: bool = False


@dataclasses.dataclass(frozen=True)
class Branches:
    """A cond/switch: one signature list per branch, plus whether the
    predicate is (transitively) derived from ``lax.axis_index``."""

    options: tuple         # tuple of tuples of signature nodes
    pred_rank_dependent: bool
    path: str
    source: str


@dataclasses.dataclass(frozen=True)
class DonationSite:
    """A pjit equation with donated invars (check C4's input)."""

    name: str              # pjit name param
    path: str
    source: str
    jaxpr: object          # the pjit's ClosedJaxpr
    donated: tuple         # per-invar donation flags


@dataclasses.dataclass
class Extraction:
    """Everything the checks consume, from one traced program."""

    signature: tuple       # nested Collective/Loop/Branches nodes
    donation_sites: list
    axis_names_seen: set   # every axis name any collective referenced
    #: program-order event list for C7: ``("flops", weight)`` runs
    #: (consecutive compute merged) interleaved with
    #: ``("coll", prim, axes, path, source)`` issue points.
    profile: tuple = ()


def _source_of(eqn):
    try:
        from jax._src import source_info_util

        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return ""


def _closed(j):
    """Normalize Jaxpr vs ClosedJaxpr (remat2 carries a raw Jaxpr)."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _axis_names(eqn):
    params = eqn.params
    axes = params.get("axes", params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _aval(v):
    return v.aval


def _is_literal(v):
    return not hasattr(v, "count")


class _Walker:
    """One recursive walk over a jaxpr tree, threading the rank-taint
    environment through every sub-jaxpr."""

    def __init__(self):
        self.donation_sites = []
        self.axis_names_seen = set()

    def walk(self, closed_jaxpr, in_taint, path=""):
        """Returns ``(signature_nodes, out_taints)`` for one jaxpr given
        per-invar taint flags."""
        jaxpr = _closed(closed_jaxpr)
        taint = {}

        def get_t(v):
            return False if _is_literal(v) else taint.get(v, False)

        def set_t(v, t):
            taint[v] = bool(t)

        for var, t in zip(jaxpr.invars, in_taint):
            set_t(var, t)
        for var in jaxpr.constvars:
            set_t(var, False)

        nodes = []
        producers = {}
        consumers = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not _is_literal(v):
                    consumers.setdefault(v, []).append(eqn)
            for v in eqn.outvars:
                producers[v] = eqn

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            in_t = [get_t(v) for v in eqn.invars]
            out_t = any(in_t) or prim == "axis_index"
            per_out_t = None  # vector taint when a handler provides one

            if prim in COLLECTIVE_PRIMS:
                nodes.append(self._collective(
                    eqn, path, producers, consumers, jaxpr))
            elif prim == "scan":
                sub_nodes, per_out_t = self._scan(eqn, in_t, path)
                nodes.extend(sub_nodes)
            elif prim == "while":
                sub_nodes, per_out_t = self._while(eqn, in_t, path)
                nodes.extend(sub_nodes)
            elif prim == "cond":
                node, out_t = self._cond(eqn, in_t, path)
                if node is not None:
                    nodes.append(node)
            else:
                sub = self._sub_jaxprs(eqn)
                if sub:
                    # pjit / remat2 / custom_{jvp,vjp}_call / anything
                    # else carrying a body: inline it (transparent
                    # control flow). Taints map positionally when arity
                    # lines up; otherwise fall back to the conservative
                    # any() join.
                    if _is_jit(eqn):
                        self._record_donation(eqn, path)
                    label = _body_label(eqn)
                    sub_path = f"{path}/{label}" if path else label
                    merged_out = False
                    for s in sub:
                        sj = _closed(s)
                        st = (in_t if len(sj.invars) == len(in_t)
                              else [any(in_t)] * len(sj.invars))
                        sub_nodes, sub_out = self.walk(s, st, sub_path)
                        nodes.extend(sub_nodes)
                        merged_out = merged_out or any(sub_out)
                    out_t = out_t or merged_out

            if per_out_t is not None and len(per_out_t) == len(eqn.outvars):
                for v, t in zip(eqn.outvars, per_out_t):
                    set_t(v, t)
            else:
                for v in eqn.outvars:
                    set_t(v, out_t)

        return tuple(nodes), [get_t(v) for v in jaxpr.outvars]

    # ---- per-primitive handlers --------------------------------------

    def _collective(self, eqn, path, producers, consumers, jaxpr):
        axes = _axis_names(eqn)
        self.axis_names_seen.update(axes)
        prim = eqn.primitive.name
        operands = [v for v in eqn.invars if not _is_literal(v)]
        dtypes = []
        nelems = 0
        for v in operands:
            aval = _aval(v)
            dtypes.append(str(aval.dtype))
            nelems += int(max(1, _size(aval)))
        dtype = ",".join(sorted(set(dtypes))) if dtypes else ""

        upcast_from = ""
        roundtrip = False
        if COLLECTIVE_PRIMS[prim] is not None and operands:
            src = producers.get(operands[0])
            if (src is not None
                    and src.primitive.name == "convert_element_type"
                    and src.invars and not _is_literal(src.invars[0])):
                from_dt = str(_aval(src.invars[0]).dtype)
                if (from_dt in _NARROW
                        and str(_aval(operands[0]).dtype) == "float32"):
                    upcast_from = from_dt
                    roundtrip = self._is_roundtrip(
                        eqn, from_dt, consumers, jaxpr)

        return Collective(
            prim=prim, axes=axes, dtype=dtype, nelems=nelems,
            reduce_op=COLLECTIVE_PRIMS[prim] or "",
            path=path or "<top>", source=_source_of(eqn),
            upcast_from=upcast_from, roundtrip=roundtrip)

    def _is_roundtrip(self, eqn, from_dt, consumers, jaxpr):
        """True iff every use of the reduction's result immediately
        casts back to the pre-upcast dtype and the raw f32 value never
        escapes as a program output — the deliberate f32-accumulate
        pattern the pipeline ``share()`` uses."""
        outs = set(jaxpr.outvars)
        for v in eqn.outvars:
            if v in outs:
                return False
            uses = consumers.get(v, [])
            if not uses:
                continue
            for use in uses:
                if (use.primitive.name != "convert_element_type"
                        or str(use.params.get("new_dtype")) != from_dt):
                    return False
        return True

    def _scan(self, eqn, in_t, path):
        p = eqn.params
        body = p["jaxpr"]
        n_in = len(_closed(body).invars)
        taints = (in_t if len(in_t) == n_in else [any(in_t)] * n_in)
        # Fixpoint over the carry: a tainted carry output taints the
        # next iteration's carry input.
        nc, ncar = p.get("num_consts", 0), p.get("num_carry", 0)
        sub_path = f"{path}/scan" if path else "scan"
        n_donations = len(self.donation_sites)
        for _ in range(3):
            # Re-walks during the taint fixpoint must not duplicate
            # recorded donation sites.
            del self.donation_sites[n_donations:]
            nodes, out_t = self.walk(body, taints, sub_path)
            new = list(taints)
            carried = out_t[:ncar]
            changed = False
            for i, t in enumerate(carried):
                if t and not new[nc + i]:
                    new[nc + i] = True
                    changed = True
            taints = new
            if not changed:
                break
        # Scan outputs = [carries..., stacked ys...]; the body's out
        # taints align 1:1, so loop-computed rank dependence survives
        # into downstream predicates (C1's guaranteed-divergence
        # classification needs this).
        if not nodes:
            return [], out_t
        return [Loop(body=nodes, length=p.get("length"), path=sub_path,
                     source=_source_of(eqn))], out_t

    def _while(self, eqn, in_t, path):
        p = eqn.params
        sub_path = f"{path}/while" if path else "while"
        n_carry = len(_closed(p["body_jaxpr"]).outvars)
        taints = list(in_t)
        n_donations = len(self.donation_sites)
        trip_rank_dep = False
        out = []
        body_out_t = None
        # Fixpoint over the carry (mirrors _scan): a tainted carry
        # output taints the next iteration's carry input — and,
        # through the cond, possibly the trip count itself.
        for _ in range(3):
            # Re-walks during the taint fixpoint must not duplicate
            # recorded donation sites.
            del self.donation_sites[n_donations:]
            out = []
            for key in ("cond_jaxpr", "body_jaxpr"):
                body = p[key]
                n_in = len(_closed(body).invars)
                sub_t = (taints[-n_in:] if len(taints) >= n_in
                         else [any(taints)] * n_in)
                nodes, o_t = self.walk(body, sub_t, sub_path)
                out.extend(nodes)
                if key == "cond_jaxpr":
                    # The cond's output IS the loop predicate: taint
                    # here means the trip count diverges by rank (C8).
                    trip_rank_dep = trip_rank_dep or any(o_t)
                else:
                    # While outputs are the carry, which the body
                    # re-emits.
                    body_out_t = o_t
            changed = False
            if len(taints) >= n_carry and len(body_out_t) == n_carry:
                base = len(taints) - n_carry
                for i, t in enumerate(body_out_t):
                    if t and not taints[base + i]:
                        taints[base + i] = True
                        changed = True
            if not changed:
                break
        if not out:
            return [], body_out_t
        return [Loop(body=tuple(out), length=None, path=sub_path,
                     source=_source_of(eqn),
                     trip_rank_dependent=trip_rank_dep)], body_out_t

    def _cond(self, eqn, in_t, path):
        branches = eqn.params["branches"]
        pred_t = in_t[0] if in_t else False
        sub_path = f"{path}/cond" if path else "cond"
        options = []
        out_t = pred_t
        for b in branches:
            n_in = len(_closed(b).invars)
            args_t = in_t[1:]
            taints = (args_t if len(args_t) == n_in
                      else [any(args_t)] * n_in)
            nodes, b_out = self.walk(b, taints, sub_path)
            options.append(nodes)
            out_t = out_t or any(b_out)
        if not any(options):
            return None, out_t
        return Branches(options=tuple(options),
                        pred_rank_dependent=bool(pred_t),
                        path=sub_path, source=_source_of(eqn)), out_t

    def _record_donation(self, eqn, path):
        donated = eqn.params.get("donated_invars")
        if donated and any(donated):
            name = str(eqn.params.get("name", ""))
            self.donation_sites.append(DonationSite(
                name=name,
                path=f"{path}/pjit:{name}" if path else f"pjit:{name}",
                source=_source_of(eqn), jaxpr=eqn.params["jaxpr"],
                donated=tuple(donated)))

    @staticmethod
    def _sub_jaxprs(eqn):
        """Every Jaxpr/ClosedJaxpr reachable from this eqn's params
        (generic: covers pjit, remat2, custom_vjp_call, and any future
        primitive that carries a body)."""
        found = []
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                found.append(v)
            elif isinstance(v, (tuple, list)):
                found.extend(x for x in v
                             if hasattr(x, "eqns") or hasattr(x, "jaxpr"))
        return found


def _is_jit(eqn):
    """A ``jax.jit`` call site, known by what it carries."""
    return "donated_invars" in eqn.params and "jaxpr" in eqn.params


def _body_label(eqn):
    """Path piece for a body-carrying equation."""
    if _is_jit(eqn) and "name" in eqn.params:
        return f"pjit:{eqn.params['name']}"
    return eqn.primitive.name


def _size(aval):
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


#: elementwise / reduction primitives whose flop weight is their output
#: element count. Deliberately coarse: C7 reasons about WHERE the
#: arithmetic mass sits relative to the collectives, not about absolute
#: flop counts, so one-flop-per-output-element is plenty.
_FLOP_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "integer_pow",
    "exp", "log", "log1p", "tanh", "logistic", "erf", "rsqrt", "sqrt",
    "neg", "abs", "sign", "select_n", "clamp",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "cumsum",
})


def _flop_weight(eqn):
    """Static flop estimate for one equation (0 = not compute).

    ``dot_general`` counts ``2 * out_elems * K`` (one multiply-add per
    contracted element); conv counts ``2 * out_elems`` per-position;
    the elementwise/reduction allowlist counts one flop per output
    element. Movement, layout, and control-flow primitives weigh zero —
    the profile measures where the arithmetic sits, not how many bytes
    shuffle around it.
    """
    name = eqn.primitive.name
    out = 0
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        if hasattr(aval, "shape"):
            out += _size(aval)
    if name == "dot_general":
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs = _aval(eqn.invars[0])
        k = 1
        for d in lhs_contract:
            k *= int(lhs.shape[d])
        return 2 * out * max(1, k)
    if name == "conv_general_dilated":
        return 2 * out
    if name in _FLOP_ELEMENTWISE:
        return out
    return 0


def build_profile(closed_jaxpr, path=""):
    """Flatten a jaxpr into C7's program-order compute/collective
    profile: ``("flops", weight)`` events (consecutive compute merged)
    interleaved with ``("coll", prim, axes, path, source)`` issue
    points. Control flow mirrors :func:`linearize`: scan bodies repeat
    by their static trip count, while loops expand once, cond takes the
    first branch (a diverging branch is C1's to reject), and every
    body-carrying primitive (pjit / remat2 / custom-vjp) inlines."""
    jaxpr = _closed(closed_jaxpr)
    out = []

    def emit_flops(n):
        if n <= 0:
            return
        if out and out[-1][0] == "flops":
            out[-1] = ("flops", out[-1][1] + n)
        else:
            out.append(("flops", n))

    def emit_all(events, repeat=1):
        for _ in range(repeat):
            for ev in events:
                if ev[0] == "flops":
                    emit_flops(ev[1])
                else:
                    out.append(ev)

    def sub(label):
        return f"{path}/{label}" if path else label

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in COLLECTIVE_PRIMS:
            out.append(("coll", prim, _axis_names(eqn),
                        path or "<top>", _source_of(eqn)))
        elif prim == "scan":
            body = build_profile(eqn.params["jaxpr"], sub("scan"))
            emit_all(body, repeat=int(eqn.params.get("length") or 1))
        elif prim == "while":
            for key in ("cond_jaxpr", "body_jaxpr"):
                emit_all(build_profile(eqn.params[key], sub("while")))
        elif prim == "cond":
            branches = eqn.params["branches"]
            if branches:
                emit_all(build_profile(branches[0], sub("cond")))
        else:
            bodies = _Walker._sub_jaxprs(eqn)
            if bodies:
                label = _body_label(eqn)
                for s in bodies:
                    emit_all(build_profile(s, sub(label)))
            else:
                emit_flops(_flop_weight(eqn))
    return tuple(out)


def extract(closed_jaxpr):
    """Walk a ClosedJaxpr and return its :class:`Extraction`."""
    w = _Walker()
    jaxpr = _closed(closed_jaxpr)
    sig, _ = w.walk(closed_jaxpr, [False] * len(jaxpr.invars))
    return Extraction(signature=sig, donation_sites=w.donation_sites,
                      axis_names_seen=w.axis_names_seen,
                      profile=build_profile(closed_jaxpr))


def linearize(nodes, _depth=0):
    """Flatten a signature tree into the ordered list of collectives one
    rank executes: loops expand by their trip count (unknown trip counts
    expand once — good enough for presence checks, and pipeline
    programs always scan with static length), branches inline when all
    options agree (a diverging branch is C1's job to reject first — here
    the first option stands in)."""
    if _depth > 64:
        raise RecursionError("signature nesting too deep")
    out = []
    for node in nodes:
        if isinstance(node, Collective):
            out.append(node)
        elif isinstance(node, Loop):
            body = linearize(node.body, _depth + 1)
            out.extend(body * (node.length if node.length else 1))
        elif isinstance(node, Branches):
            if node.options:
                out.extend(linearize(node.options[0], _depth + 1))
    return out


def iter_nodes(nodes):
    """Depth-first iteration over every node in a signature tree."""
    for node in nodes:
        yield node
        if isinstance(node, Loop):
            yield from iter_nodes(node.body)
        elif isinstance(node, Branches):
            for opt in node.options:
                yield from iter_nodes(opt)
