"""Mean host time of one ``db.commit(wave)`` call, in ms: the
``bench.commit`` span (pack, dispatch, device and outcome sync)."""


def read(ctx):
    d = ctx.trace.span_seconds("bench.commit")
    return 1e3 * sum(d) / len(d) if d else None
