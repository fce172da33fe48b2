"""Share of the bf16 peak reached by the held experts' grouped matmuls:
the operations the window's routed rows need (the ``moe_rows`` counter
summed over the window's steps, times ``bench.work_moe.gmm_flops_per_row``,
forward and backward, recomputation not counted) over the peak times the
device seconds of the grouped-matmul kernels, found by label among the
trace's operations."""

# the labels the grouped-matmul kernels (megablox gmm, its transposed-
# operand tgmm for the weight gradient) carry in the trace
GMM_LABELS = ("gmm",)


def read(rec, ctx):
    tr = rec.trace_summary
    flops = rec.counts.get("gmm_flops_in_window")
    if tr is None or not flops:
        return None
    secs = sum(s for name, s in tr.device_ops
               if any(label in name for label in GMM_LABELS))
    if secs <= 0:
        return None
    return 100.0 * flops / (ctx["peaks"]["bf16_flops_s"] * secs)
