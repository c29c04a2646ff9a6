"""Traces and compiles JAX reported inside the join window: the
program's ``jax.compile`` records there.  Set-up warms every shape, so
any is a shape that varies from query to query."""
from bench import program_spans as ps


def read(ctx):
    return ps.compiles(ps.join(ctx))
