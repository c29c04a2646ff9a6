"""Device time of all-to-all ops per checkout wave, in ms, averaged over
the chips: the commit's prepare, grant and install exchanges between the
storage shards (``bench.commit`` spans count the waves)."""


def read(ctx):
    waves = ctx.trace.count_spans("bench.commit")
    s = ctx.trace.op_seconds(
        lambda name, opcode, prog: opcode.startswith("all-to-all"))
    return 1e3 * s / waves if waves and s > 0 else None
