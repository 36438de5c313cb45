"""Device-to-host reads per decode step in the engines' greedy decode
loops (syncs/tok): Σ ``syncs`` over Σ ``steps`` on the program's
``iemas.engine.decode`` spans. 1 where the host reads each token back
before dispatching the next step; 1/steps where the loop runs on the device
and its tokens are read back once. None where the spans carry no ``syncs``
counter."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")


def read(ctx):
    decode = program.named(program.spans(ctx) or [], "engine.decode")
    if not decode or any("syncs" not in s.stats for s in decode):
        return None
    steps = sum(s.stats["steps"] for s in decode)
    return sum(s.stats["syncs"] for s in decode) / steps if steps else None
