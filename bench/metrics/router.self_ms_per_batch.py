"""The router's own bookkeeping per routed batch (ms/batch): self time of
the program's ``iemas.route_batch`` spans, outside every span nested in
them (price book, fused step, spill round): decision building, pending
and window accounting in ``IEMASRouter.route_batch``."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")


def read(ctx):
    found = program.spans(ctx)
    batches = len(program.named(found or [], "route_batch"))
    if not batches:
        return None
    return 1e-6 * program.self_ns(found, "route_batch") / batches
