"""The GT pass's share of the chip's bf16 peak, in percent: vit-l16
forward operations (``bench/costs/vit_l16.py``) of every real crop
classified in the window, over the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("real_crops"):
        return None
    flops = c["real_crops"] * c["gt_flops_per_crop"]
    return 100.0 * flops / ctx["seconds"] / ctx["peaks"]["bf16_flops"]
