"""Host assembly of the fused routing step per routed batch (ms/batch):
wall time of the program's ``iemas.fused.assemble`` spans (ledger and
forest mirror syncs, request packing, blend table, price grid: everything
before the program call) over the window's ``iemas.route_batch`` spans."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")


def read(ctx):
    found = program.spans(ctx)
    batches = len(program.named(found or [], "route_batch"))
    if not batches or not program.named(found, "fused.assemble"):
        return None
    return 1e-6 * program.wall_ns(found, "fused.assemble") / batches
