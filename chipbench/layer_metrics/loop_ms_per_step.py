"""Device time of what running ONE stack of layers several times costs
beside running its layers, per traced step: the scope ``hvd.loop`` (the
norm that closes a trip and its backward; the sum of the shared leaves'
gradients over the trips, float32 and rounded once), all phases
(``chipbench/scopes.py``). ``None`` for a program that has no scope
tables or no loop."""

from chipbench import scopes


def read(ctx):
    try:
        return scopes.ms_per_step(ctx, "loop")
    except ValueError:       # a program from before the loop
        return None
