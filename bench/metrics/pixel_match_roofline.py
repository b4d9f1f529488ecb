"""``pixel_match``'s share of its roofline, in percent: for every call in
the traced window, the least time the chip could take (operations over
peak or bytes over bandwidth, ``bench/costs/pixel_match.py``) over the
device time of the call's program."""
from bench.costs.pixel_match import cost
from bench.metrics_lib import roofline


def read(ctx):
    def cost_of(outs, ins):
        (na, d), (nb, _) = ins[-2], ins[-1]
        return cost(na, nb, d)
    return roofline(ctx, "pixel_match", cost_of)
