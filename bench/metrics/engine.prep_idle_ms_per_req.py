"""Device idle time while an engine prepares a request, per served request
(ms/req): the time no chip ran an operation inside the program's
``iemas.engine.serve`` spans but outside their prefill/extend/decode
children (session pick, LCP, padding, uploads, the stored prompt, LRU
eviction), over the serves."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")

CHILDREN = ("engine.prefill", "engine.extend", "engine.decode")


def read(ctx):
    found = program.spans(ctx) or []
    serves = program.named(found, "engine.serve")
    if not serves:
        return None
    inner = [s for s in found if s.name in CHILDREN]
    idle = program.idle_ns(ctx, program.minus(serves, inner))
    return 1e-6 * idle / len(serves)
