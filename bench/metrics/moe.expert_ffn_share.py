"""The MoE's grouped expert kernel's share of the served model's device
time (%): the device time of the ``moe_gmm`` operations inside
``bench.serve`` spans over the device-seconds busy inside them, summed over
the chips. None where the trace has no such kernel."""
import loader

reduce = loader.module(loader.BENCH / "trace" / "reduce.py")
KERNEL = "moe_gmm"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    serve = tr.spans.get("bench.serve", [])
    busy = tr.busy_in_sum("bench.serve")
    kernel = reduce.overlap(reduce.merge(tr.kernel_events(KERNEL)), serve)
    if busy <= 0 or kernel <= 0:
        return None
    return 100.0 * kernel * 1e-9 / busy
