"""``dequant_topk``'s share of its roofline, in percent, as
``pixel_match_roofline`` reads its own (``bench/costs/dequant_topk.py``)."""
from bench.costs.dequant_topk import cost
from bench.metrics_lib import roofline


def read(ctx):
    def cost_of(outs, ins):
        (m, c), (_, k) = ins[-2], outs[0]
        return cost(m, c, k)
    return roofline(ctx, "dequant_topk", cost_of)
