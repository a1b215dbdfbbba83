"""The share of the chip's busy time, over the window of whole steps,
whose instruction resolves to a name of ``spans.SCOPES``
(``chipbench/scopes.py``: the join and its arithmetic), in percent. What
is left is listed by instruction on the run's ``scopes`` line
(``unscoped_ms``) and by ``scopes.py --report``. ``.lm`` and ``.cnn``
share this reader. ``None`` for a program that has no scope tables."""

from chipbench import scopes


def read(ctx):
    return scopes.coverage_pct(ctx)
