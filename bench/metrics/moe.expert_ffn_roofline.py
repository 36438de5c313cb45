"""Share of its roofline that the MoE's grouped expert kernel reaches (%).

Kernel time: the device durations of the ``moe_gmm`` operations (the
program's Pallas kernel, `repro.kernels.moe_gmm`) inside ``bench.serve``
spans. Work: the ``moe_rows`` and ``moe_experts`` counters of the engines'
``iemas.engine.prefill``/``extend``/``decode`` spans (each summed over the
MoE layers and decode steps of its call), counted by the model family's
``expert_ffn_counts``: 2·3·D·F operations a row, the weights of each expert
that got rows read once and the rows read and written. The least time is
the larger of operations over peak FLOP/s and bytes over peak bandwidth.
None where the trace has no such kernel or the spans no such counters (a
program without the grouped kernel)."""
import loader

program = loader.module(loader.BENCH / "trace" / "program.py")
reduce = loader.module(loader.BENCH / "trace" / "reduce.py")
KERNEL = "moe_gmm"
SPANS = ("engine.prefill", "engine.extend", "engine.decode")


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.model is None:
        return None
    family = loader.reference(ctx.model["engine"]["family"])
    found = [s for s in program.spans(ctx) or [] if s.name in SPANS]
    if not hasattr(family, "expert_ffn_counts") or not found or any(
            "moe_rows" not in s.stats for s in found):
        return None
    t = reduce.overlap(reduce.merge(tr.kernel_events(KERNEL)),
                       tr.spans.get("bench.serve", [])) * 1e-9
    if t <= 0:
        return None
    ops, nbytes = family.expert_ffn_counts(
        ctx.model, sum(s.stats["moe_rows"] for s in found),
        sum(s.stats["moe_experts"] for s in found))
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
