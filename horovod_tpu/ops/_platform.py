"""Pallas kernel or reference math: one decision, taken from where the
operands live.

The kernel wrappers (``flash_attention``, ``decode_attention``, the
grouped-MoE matmul, the ring/ulysses ``use_flash`` default) all ask
:func:`use_pallas`. It answers from the device the operands are on —
never from a guess that keeps "passing" on the reference path after a
run has lost its chip — and a TPU run that would fall into interpret
mode raises instead of crawling.
"""

import jax


def operand_platform(*operands):
    """Platform of the device the operands live on.

    A concrete ``jax.Array`` knows its devices. A tracer does not: the
    program being traced runs where jit places it, which is the default
    backend unless the caller placed its arguments elsewhere (compile-
    only tests for a described topology patch this function).
    """
    for x in operands:
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            return next(iter(x.devices())).platform
    return jax.default_backend()


def use_pallas(what, operands, interpret=False):
    """True → run the pallas kernel (compiled on a TPU, interpreted when
    a test set the module's ``_INTERPRET``); False → the XLA reference
    math, which only non-TPU operands may take."""
    on_tpu = operand_platform(*operands) == "tpu"
    if on_tpu and interpret:
        raise RuntimeError(
            f"{what}: pallas interpret mode is a CPU test device, but "
            "the operands live on a TPU — unset _INTERPRET")
    return on_tpu or interpret
