"""Model FLOP utilization of the served window (%): the operations the
forward passes need for the tokens actually computed (prefill or extend of
each prompt's uncached tokens, then one decode step per generated token,
by `counts.served_request`), over the traced window times the bf16 peak of
every chip the cell holds (a chip that serves nothing still counts)."""


def read(ctx):
    tr = ctx.trace
    if ctx.model is None or tr is None or not ctx.served or not tr.chips:
        return None
    ops = sum(ctx.counts.served_request(ctx.model, r["n_prompt"], r["n_hit"],
                                        r["n_gen"])[0] for r in ctx.served)
    return 100.0 * ops / (tr.window_s * tr.chips
                          * ctx.peaks["bf16_flops_per_s"])
