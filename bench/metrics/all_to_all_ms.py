"""Device time of all-to-all ops per query, in ms, averaged over the
chips: the route's exchange between chips."""


def read(ctx):
    queries = ctx.trace.count_spans("bench.execute")
    s = ctx.trace.op_seconds(
        lambda name, opcode, prog: opcode.startswith("all-to-all"))
    return 1e3 * s / queries if queries and s > 0 else None
