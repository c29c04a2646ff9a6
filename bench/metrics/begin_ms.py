"""Host time of a checkout wave's ``Session.begin`` calls, in ms: the
generator's clock around each ``db.session().begin()`` (the session's
set-up and its read snapshot from the timestamp oracle), summed over the
wave's sessions and averaged over the window's waves.  It reads wherever
the window holds a wave, whether the begins sync with the device or
not."""


def read(ctx):
    waves = [w for w in ctx.cell.waves[ctx.cell.first:] if not w.failed]
    return 1e3 * sum(w.begin_s for w in waves) / len(waves) \
        if waves else None
