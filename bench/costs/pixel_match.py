"""Operations and bytes of one ``pixel_match`` call, from its shapes.

``(Na, D)`` crops against ``(Nb, D)`` references, both padded to the
kernel's tiles: the crop tile stays in VMEM while every reference tile
streams past it once per crop tile. Each (crop, reference, element)
triple costs a subtract, an absolute value and an add.
"""


def cost(na: int, nb: int, d: int, ba: int = 128, bn: int = 128):
    ba, bn = min(ba, max(8, na)), min(bn, max(8, nb))
    nap, nbp = -(-na // ba) * ba, -(-nb // bn) * bn
    ops = 3.0 * nap * nbp * d
    bytes_ = 4.0 * (nap * d + (nap // ba) * nbp * d + 3 * nap)
    return ops, bytes_
