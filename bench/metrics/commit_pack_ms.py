"""Host time packing a checkout wave for the commit, in ms: the program's
``namdb.commit.pack`` span (numpy packing, the CID claim and its sync, the
host-to-device copies) per ``namdb.commit`` in the window."""
from bench import program_spans as ps


def read(ctx):
    return ps.per_unit(ps.checkout(ctx),
                       lambda n: n == "namdb.commit.pack", ps.ms)
