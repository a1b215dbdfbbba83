"""Seconds from the start of the process, by the kernel's account, to
the moment the dispatch of its first step begins: the program's own
start-up mark ``hvd.step.first`` (``horovod_tpu.utils.spans.marks()``;
docs/metrics.md "Set-up: the compile log and the start-up marks").
Imports, the backend, the weights and the benchmark's own lowering of
the grad program lie before it; in the eager lane the mark is stamped
in ``allreduce_gradients``, so there it also holds the user's grad
program, traced, lowered and compiled or read. The benchmark's ``setup_s``
starts a little earlier, in the parent (``run.py``). ``None`` for a
program without the marks."""


def mark(name):
    """The start-up mark ``hvd.<name>`` in seconds since the process
    began; ``None`` where the program has no marks or has not reached
    this one (the readers name a mark without its ``hvd.``, as they name
    a scope)."""
    try:
        from horovod_tpu.utils.spans import marks
    except ImportError:
        return None
    return marks().get("hvd." + name)


def read(ctx):
    return mark("step.first")
