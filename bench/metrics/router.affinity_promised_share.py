"""Share of prompt tokens the router's Eq.-4 affinity promised from cache
(%): the sum of the ``promised`` counters (round(affinity x prompt length)
of each completed request's matched pair) over the sum of ``n_prompt``, on
the program's ``iemas.phase4_feedback`` spans. Low where the router sent
requests to agents without their prefix."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")


def read(ctx):
    fb = program.named(program.spans(ctx) or [], "phase4_feedback")
    prompt = sum(s.stats["n_prompt"] for s in fb)
    if not prompt:
        return None
    return 100.0 * sum(s.stats["promised"] for s in fb) / prompt
