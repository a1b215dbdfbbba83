"""Device time of the multi-token-prediction module's glue per traced
step: every op under the scope ``mtp`` (the two norms of the next
token's embedding and of the stream, their concatenation, ``eh_proj``,
the second loss term's shift and mask), all phases
(``chipbench/scopes.py``). The module's layers and its pass through the
head run the model's own layer programs and read under THEIR scopes
(``attn.*``, ``moe.*``, ``head``, ``loss``). ``None`` for a program
without the scope (one from before it, or a model with no such
module)."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "mtp")
    except ValueError:       # a program from before the scope
        return None
