"""Chips that served each route batch (chips/batch): the mean ``chips``
counter on the program's ``iemas.dispatch`` spans, one a batch, each the
number of devices holding weights that the batch's requests went to. 1
where the batch is served one request after another on one chip; more where
one batch keeps several chips busy at once. None where the program writes
no such span."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")


def read(ctx):
    dispatch = program.named(program.spans(ctx) or [], "dispatch")
    if not dispatch:
        return None
    return sum(s.stats["chips"] for s in dispatch) / len(dispatch)
