"""Share of the promised cache hits the engines kept (%): the sum over
completed requests of min(``n_hit``, ``promised``) over the sum of
``promised``, on the program's ``iemas.phase4_feedback`` spans. Low where
the router promised a prefix that the engine's LRU had already evicted."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")


def read(ctx):
    fb = program.named(program.spans(ctx) or [], "phase4_feedback")
    promised = sum(s.stats["promised"] for s in fb)
    if not promised:
        return None
    return 100.0 * sum(min(s.stats["n_hit"], s.stats["promised"])
                       for s in fb) / promised
