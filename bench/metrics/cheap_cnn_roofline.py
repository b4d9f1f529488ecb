"""The cheap CNN's share of the chip's bf16 peak inside the sharded
megastep, in percent: ``cnn.rows`` counted while the profiler ran, times
the CNN's operations per row (``bench/costs/<model>.py``), over the peak,
over the device seconds of the ``ingest_megastep`` program in the traced
window. That program also runs ``centroid_assign`` and the matched fold,
so the share reads low, never high."""


def read(ctx):
    c = ctx["counters"]
    t = ctx.get("trace") or {}
    if "cnn.rows.trace_stop" not in c or not c.get("cnn_flops_per_row"):
        return None
    rows = c["cnn.rows.trace_stop"] - c["cnn.rows.trace_start"]
    spent = sum(s for m, s in (t.get("module_s") or {}).items()
                if "ingest_megastep" in m)
    if rows <= 0 or spent <= 0:
        return None
    least = rows * c["cnn_flops_per_row"] / ctx["peaks"]["bf16_flops"]
    return 100.0 * least / spent
