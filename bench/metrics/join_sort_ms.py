"""Device time of sort ops per query, in ms, averaged over the chips: the
local radix passes and the build side's sort, each a stable sort that
carries its relation's payload columns as operands."""


def read(ctx):
    queries = ctx.trace.count_spans("bench.execute")
    s = ctx.trace.op_seconds(lambda name, opcode, prog: opcode == "sort")
    return 1e3 * s / queries if queries and s > 0 else None
