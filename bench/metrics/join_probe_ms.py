"""Device time of the sort-probe per query, in ms, averaged over the
chips: the ``while`` loop of the local join's ``searchsorted``, a binary
search of gathers over the probe side, and the only loop on the join
path."""


def read(ctx):
    queries = ctx.trace.count_spans("bench.execute")
    s = ctx.trace.union_seconds(lambda name, opcode, prog: opcode == "while")
    return 1e3 * s / queries if queries and s > 0 else None
