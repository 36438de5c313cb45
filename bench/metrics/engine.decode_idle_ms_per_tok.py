"""Device idle time inside the engines' greedy decode loops, per decode
step (ms/tok): the time no chip ran an operation inside the
program's ``iemas.engine.decode`` spans, over the sum of their ``steps``
counters. The host-synced token loop's loss per generated token."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")


def read(ctx):
    decode = program.named(program.spans(ctx) or [], "engine.decode")
    steps = sum(s.stats["steps"] for s in decode)
    if not steps:
        return None
    idle = program.idle_ns(ctx, [(s.start, s.end) for s in decode])
    return 1e-6 * idle / steps
