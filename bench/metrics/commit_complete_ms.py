"""Host time finishing a sharded commit, in ms: the program's
``namdb.commit.complete`` spans (each written table's out-of-range
bitvector flips and the re-placement of its store) per ``namdb.commit``
in the window."""
from bench import program_spans as ps


def read(ctx):
    return ps.per_unit(ps.checkout(ctx),
                       lambda n: n == "namdb.commit.complete", ps.ms)
