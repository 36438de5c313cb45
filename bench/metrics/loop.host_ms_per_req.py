"""Host time of the serving loop itself, per served request (ms/req).

The window's wall time outside the benchmark's spans around ``route_batch``,
``on_complete`` and the engines' ``serve``: event handling, admission,
request building and dispatch bookkeeping in `EventSimulator`/`SimCluster`.
"""


def read(ctx):
    if not ctx.served:
        return None
    inside = sum(t1 - t0 for name, t0, t1 in ctx.spans
                 if name in ("bench.route", "bench.feedback", "bench.serve"))
    return 1e3 * (ctx.window_s - inside) / len(ctx.served)
