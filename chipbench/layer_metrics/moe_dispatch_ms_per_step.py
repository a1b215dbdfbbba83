"""Device time of the expert layers' routing chain OUTSIDE the kernels
per traced step: top-k, the sorts of the 65,536 routed slots, the row
gathers that permute tokens into expert order and back, the group
count. Found by the kind of op, as ``by_opcode_ms`` splits the trace:
every ``sort`` (``lax.top_k`` over the 64 experts is a sort on this chip
too) plus every ``fusion`` of ``kind=kCustom``, which is how this
compiler emits a gather or a scatter with computed indices (read off a
chip trace, PR 27: four ``bf16[65536,2048]`` row gathers a layer at 2.2
ms apiece are nine tenths of it; matmul fusions are ``kOutput``,
elementwise ones ``kLoop``). Self time, so nothing is counted twice.

What else it catches: the embedding lookup, its scatter-add in the
backward pass and the loss's pick of the target logit, 1.5 ms of 35 in
OLMoE's step. What it misses: the elementwise passes around the kernels
(``silu(gate) * up``, the weighted sum over the K choices): ``kLoop``
fusions like any other. What would break it: a compiler that fuses
gathers under another kind. ``None`` where a program has neither."""

from chipbench import xplane


def is_routing(ev):
    kind = xplane.opcode(ev)
    return kind == "sort" or (kind == "fusion"
                              and "kind=kCustom" in ev.name)


def read(ctx):
    chip = ctx.chip
    if not chip.steps:
        return None
    ns = sum(chip.self_ns_by(
        lambda ev: "routing" if is_routing(ev) else None).values())
    return ns / 1e6 / chip.steps if ns else None
