"""Device time of the gated short convolutions' elementwise chains per
traced step: what runs between a ``conv`` layer's two projections
(``models/llama.py:gated_short_conv``, traced under the scope
``hvd_short_conv``), forward, again where a remat mode re-runs it, and
backward.

How it is found. On this chip an op's event is its HLO text without
``metadata=`` and its stats hold no ``op_name`` (read off a chip trace,
PR 34: ``device_offset_ps``, ``device_duration_ps`` and a time scale are
all an event carries), so the scope's name reaches no event. The chain is
found by the one type only it touches: ``[batch, seq, 3 x hidden]``, the
in-projection's ``[B, C, z]`` (no other activation of the model is that
wide). It matches the ``kind=kLoop`` fusions whose text holds that type:
the compiler's ``slice_multiply_fusion``s, which read the three streams
(forward: ``B * z``, the taps, ``C * c``; backward: the same values
again for the product rule).

What else it catches: nothing in this model. What it misses: the parts
the compiler folds into the projections' own fusions (``kind=kOutput``:
the backward's three streams are concatenated as a matmul's prologue,
the RMSNorm as another's), and a backward piece that reads only
``hidden``-wide values. What would break it: a compiler that emits the
chain under another kind of fusion, or a second activation of that
width. ``None`` for a model with no conv layer and for a program in
which nothing matches."""

from chipbench import xplane


def read(ctx):
    chip = ctx.chip
    cfg = getattr(ctx.model, "cfg", None)
    if not chip.steps or not getattr(cfg, "conv_taps", 0):
        return None
    wide = f"[{ctx.model.batch_size},{ctx.model.seq},{3 * cfg.d_model}]"

    def is_chain(ev):
        return (xplane.opcode(ev) == "fusion" and "kind=kLoop" in ev.name
                and wide in ev.name)

    ns = chip.class_ns(is_chain)
    return ns / 1e6 / chip.steps if ns else None
