"""Operations and bytes of one ``dequant_topk`` call, from its shapes.

``(M, C)`` 8-bit rows, padded to 128 lanes, are read once with their
per-row scales; each row is dequantized (a convert and a multiply per
element) and then swept ``k`` times by the max-extract-and-mask pass (a
compare, a select and a mask per element and pass); ``(M, k)`` values and
indices are written.
"""


def cost(m: int, c: int, k: int, bm: int = 128):
    bm = min(bm, max(8, m))
    mp, cp = -(-m // bm) * bm, -(-c // 128) * 128
    ops = 2.0 * mp * cp + 3.0 * k * mp * cp
    bytes_ = 1.0 * mp * cp + 4.0 * mp + 8.0 * mp * k
    return ops, bytes_
