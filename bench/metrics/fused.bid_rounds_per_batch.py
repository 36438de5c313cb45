"""Bidding rounds the fused step's auction ran per routed batch
(rounds/batch): the mean ``rounds`` counter on the program's
``iemas.fused.device`` spans (warm attempt, or the cold re-solve where the
warm attempt tripped its budget)."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")


def read(ctx):
    device = program.named(program.spans(ctx) or [], "fused.device")
    if not device:
        return None
    return sum(s.stats["rounds"] for s in device) / len(device)
