"""The whole fused routing step's share of the chip's peak (%): the
operations its batches need (Eq.-4 prefix match of every prompt token
against every agent, and each bid round over the n x m market, from
`counts.fused_route`), over the wall time of the ``route_batch`` spans
times the bf16 peak. It bounds what taking the bid kernel off the path
could gain."""
import bisect


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.route_calls:
        return None
    routes = sorted(tr.spans.get("bench.route", []))
    if len(routes) != len(ctx.route_calls):
        return None
    rounds = [0] * len(routes)
    starts = [s for s, _ in routes]
    for s, _ in tr.kernel_events("auction_bid"):
        k = bisect.bisect_right(starts, s) - 1
        if 0 <= k and s < routes[k][1]:
            rounds[k] += 1
    ops = sum(ctx.counts.fused_route(c["n"], c["m"], c["prompt_tokens"], r)
              for c, r in zip(ctx.route_calls, rounds))
    wall = sum(c["t1"] - c["t0"] for c in ctx.route_calls)
    return 100.0 * ops / (wall * ctx.peaks["bf16_flops_per_s"])
