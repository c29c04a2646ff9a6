"""Device time of the jitted RSI commit program per wave, in ms.

The program has no stable name yet: ``Database._jit_commit`` jits a
lambda, which XLA names ``jit__lambda``, and no other lambda is jitted on
the checkout path."""
from bench.trace import program

COMMIT_PROGRAM = "jit__lambda"


def read(ctx):
    waves = ctx.trace.count_spans("bench.commit")
    s = ctx.trace.module_seconds(lambda n: program(n) == COMMIT_PROGRAM)
    return 1e3 * s / waves if waves and s > 0 else None
