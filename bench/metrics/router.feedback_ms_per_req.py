"""Phase-4 feedback (predictor and ledger updates, accounts) per completion
(ms/req): the benchmark's span around ``router.on_complete``."""


def read(ctx):
    spans = [t1 - t0 for name, t0, t1 in ctx.spans if name == "bench.feedback"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
