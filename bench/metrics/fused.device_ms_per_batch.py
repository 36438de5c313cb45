"""Device time inside ``route_batch`` spans, per routed batch (ms): the
fused routing program and the host-to-device uploads it waits on, in
device-seconds summed over the chips (the router runs on one of them)."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.spans.get("bench.route"):
        return None
    return 1e3 * tr.busy_in_sum("bench.route") / len(tr.spans["bench.route"])
