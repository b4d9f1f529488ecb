"""Forward operations of the GT ViT per crop, from its sizes."""


def flops_per_crop(cfg: dict) -> float:
    """2 x multiply-adds: patch embedding, per layer the four attention
    projections, the scores and the weighted sum (4 S^2 d), the MLP, and
    the head on the CLS token."""
    d, f, p = cfg["d_model"], cfg["d_ff"], cfg["patch"]
    n = (cfg["img_res"] // p) ** 2
    S = n + 1
    patch = 2 * n * p * p * 3 * d
    layer = 2 * S * (4 * d * d + 2 * d * f) + 4 * S * S * d
    return float(patch + cfg["n_layers"] * layer + 2 * d * cfg["n_classes"])
