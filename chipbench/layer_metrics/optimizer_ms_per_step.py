"""Device time of the apply program per traced step, from the module
line: every program execution in the window that is neither the anchor
(the grad program, the one that takes most time) nor a collective's."""


def read(ctx):
    chip = ctx.chip
    if not chip.steps:
        return None
    names = {m.name for m in chip.modules}
    apply = [n for n in names if "apply" in n]
    if not apply:
        return None
    return chip.module_ns(lambda m: m.name in apply) / 1e6 / chip.steps
