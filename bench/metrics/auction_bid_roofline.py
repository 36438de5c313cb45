"""Share of its roofline that the Phase-2 bid kernel reaches (%).

Kernel time: the device durations of the trace events of the Pallas bid
kernel (``KERNEL``). Work: each event is one bidding round of the batch
whose ``route_batch`` span holds it, counted over that batch's own n x m
market by `counts.auction_bid` (not the padded tiles). The least time is
the larger of operations over peak FLOP/s and bytes over peak bandwidth;
for a bidding round the bytes bound it.
"""
import bisect

#: the kernel's name in the device trace
KERNEL = "auction_bid"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    events = tr.kernel_events(KERNEL)
    routes = sorted(tr.spans.get("bench.route", []))
    if not events or len(routes) != len(ctx.route_calls):
        return None
    starts = [s for s, _ in routes]
    ops = nbytes = 0
    for s, _ in events:
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= routes[k][1]:
            return None           # a bid round outside any routing span
        call = ctx.route_calls[k]
        o, b = ctx.counts.auction_bid(call["n"], call["m"])
        ops, nbytes = ops + o, nbytes + b
    t = sum(e - s for s, e in events) * 1e-9
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
