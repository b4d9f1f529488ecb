"""Rows the GT model ran per real crop: padding to the GT batch's 64-row
buckets is the waste."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("real_crops"):
        return None
    return c["padded_rows"] / c["real_crops"]
