"""Host time of a checkout wave's ``Session.begin`` calls taking their
read snapshots, in ms: the program's ``namdb.sync.oracle`` spans tagged
with a ``session`` (the oracle reads of ``Session.begin``) per
``namdb.commit`` (one per wave) in the window."""
from bench import program_spans as ps


def read(ctx):
    return ps.per_unit(ps.checkout(ctx), lambda n: n == "namdb.sync.oracle",
                       ps.ms, keep=lambda r: "session" in r.ids)
