"""Share of prompt tokens served from an agent's cached prefix (%):
sum of cached tokens over sum of prompt tokens, over the window."""


def read(ctx):
    n = sum(r["n_prompt"] for r in ctx.served)
    return 100.0 * sum(r["n_hit"] for r in ctx.served) / n if n else None
