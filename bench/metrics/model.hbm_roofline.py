"""The engine programs' share of the HBM roofline (%): the bytes the served
requests' prefill/extend/decode calls must move (weights once per call, the
cached keys and values read, the new ones written; `counts.served_request`)
over the device-seconds busy inside ``serve`` spans, summed over the chips
(each agent's calls run on its own chip), times one chip's peak bandwidth."""


def read(ctx):
    if ctx.model is None or ctx.trace is None or not ctx.served:
        return None
    busy = ctx.trace.busy_in_sum("bench.serve")
    if busy <= 0:
        return None
    nbytes = sum(ctx.counts.served_request(ctx.model, r["n_prompt"],
                                           r["n_hit"], r["n_gen"])[1]
                 for r in ctx.served)
    return 100.0 * nbytes / (busy * ctx.peaks["hbm_bytes_per_s"])
