"""The ingest step's share of the chip's bf16 peak, in percent: cheap-CNN
forward operations (``bench/costs/spec1.py``) of every CNN row of the
window, over the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("cnn_rows"):
        return None
    flops = c["cnn_rows"] * c["cnn_flops_per_row"]
    return 100.0 * flops / ctx["seconds"] / ctx["peaks"]["bf16_flops"]
