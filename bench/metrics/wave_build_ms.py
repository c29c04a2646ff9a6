"""Mean host time of one checkout wave's build, in ms: the ``bench.wave_build``
span around its ``db.snapshot_read`` and its sessions' ``begin``/``put``."""


def read(ctx):
    d = ctx.trace.span_seconds("bench.wave_build")
    return 1e3 * sum(d) / len(d) if d else None
