"""Forward operations of ResNet-18 (the cheap CNN) per crop, from its
sizes: the work of its ``input_res`` input, whatever the crop's own."""
from bench.reference.resnet18 import plan


def flops_per_crop(cfg: dict, n_classes: int) -> int:
    """2 x multiply-adds of every conv, projection and the dense head
    (3.63e9 at 224 px with 7 classes)."""
    res = -(-int(cfg["input_res"]) // 2)              # 7x7 stride-2 stem
    total = 2 * res * res * 49 * 3 * cfg["stem_width"]
    res = -(-res // 2)                                 # 3x3 stride-2 pool
    for stage in plan(cfg):
        for ci, co, s in stage:
            res = -(-res // s)
            total += 2 * res * res * 9 * (ci * co + co * co)
            if s != 1 or ci != co:
                total += 2 * res * res * ci * co
    return total + 2 * cfg["feature_dim"] * n_classes
