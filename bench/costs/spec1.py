"""Forward operations of the cheap CNN per crop, from its sizes."""
from bench.reference.cnn import plan


def flops_per_crop(cfg: dict, n_classes: int) -> int:
    """2 x multiply-adds of the convs, the feature dense and the head."""
    total, res = 0, int(cfg["input_res"])
    for ci, co, s in plan(cfg):
        res //= s
        total += 2 * res * res * 9 * ci * co
    c_last = plan(cfg)[-1][1]
    return total + 2 * c_last * cfg["feature_dim"] \
        + 2 * cfg["feature_dim"] * n_classes
