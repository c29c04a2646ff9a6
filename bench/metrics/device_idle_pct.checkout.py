"""Share of the checkout window in which the chip ran no op, in %."""


def read(ctx):
    return ctx.trace.idle_pct() if ctx.trace.busy_s() > 0 else None
