"""Traces and compiles JAX reported inside the checkout window: the
program's ``jax.compile`` records there.  Set-up warms every shape, so
any is a shape that varies from wave to wave."""
from bench import program_spans as ps


def read(ctx):
    return ps.compiles(ps.checkout(ctx))
