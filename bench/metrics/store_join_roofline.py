"""Share of the HBM roofline reached by the store's join kernels: the
bytes the window's updates need joined, one row into every replica per
update put in the window, counted by ``bench.work`` (delta row in,
touched row read and written, its digest row written), over the chip's
peak bandwidth, divided by the device time of every module the
``scatter_join`` and ``fused_join_digest`` wrappers launch (copies
included). The count is the traffic's, not the rows the program happens
to pass to its joins."""


def read(rec, ctx):
    tr = rec.trace_summary
    need = rec.counts.get("join_bytes", 0)
    if tr is None or not need:
        return None
    secs = tr.module_seconds("scatter_join") \
        + tr.module_seconds("fused_join_digest")
    if secs <= 0:
        return None
    return 100.0 * need / ctx["peaks"]["hbm_bytes_s"] / secs
