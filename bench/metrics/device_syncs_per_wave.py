"""Device-to-host syncs of the facade per checkout wave: the program's
``namdb.sync.*`` spans (oracle reads, the CID claim, the outcome wait...)
per ``namdb.commit`` in the window."""
from bench import program_spans as ps


def read(ctx):
    return ps.per_unit(ps.checkout(ctx),
                       lambda n: n.startswith("namdb.sync."), ps.one)
