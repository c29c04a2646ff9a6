"""The route kernel's share of its HBM roofline, in %.

The least time is the route's contract bytes (``bench.roofline``: rows
read plus the whole wire buffer written, from the shapes the generator
routes) over the chip's HBM bandwidth; the kernel time is the device time
of the Mosaic kernels (``tpu_custom_call``) in the window, per chip.  On
the join path the route's partitioner is the only Mosaic kernel."""
from bench.roofline import least_time_s, route_bytes


def read(ctx):
    queries = ctx.trace.count_spans("bench.execute")
    kernel = ctx.trace.op_seconds(
        lambda name, opcode, prog: opcode == "tpu_custom_call")
    if not queries or kernel <= 0:
        return None
    nbytes = sum(route_bytes(r["rows"], r["field_words"], n=r["n"],
                             cap=r["cap"]) for r in ctx.cell.route_shapes())
    return 100.0 * least_time_s(nbytes, ctx.peaks) * queries / kernel
