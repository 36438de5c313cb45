"""Engine time per generated token (ms/tok): the wall time of each ``serve``
after its first token (greedy decode steps, each synced to the host),
summed, over the generated tokens."""


def read(ctx):
    if ctx.model is None or not ctx.served:
        return None
    toks = sum(r["n_gen"] for r in ctx.served)
    t = sum(r["serve_s"] - r["ttft_s"] for r in ctx.served)
    return 1e3 * t / toks if toks else None
