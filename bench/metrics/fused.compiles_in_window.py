"""Growth of the fused routing step's jit caches during the window
(`RoutingProfiler.fused_retraces`): programs traced, and compiled or read
from the persistent cache, while the window ran. 0 once the warm pass has
covered every shape bucket."""


def read(ctx):
    return None if ctx.fused_compiles is None else float(ctx.fused_compiles)
