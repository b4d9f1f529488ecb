"""Rows the cheap CNN ran per CNN row of the window: the program's
``cnn.rows`` (every row of every megastep batch, bucket padding
included) over the rows folded (``IngestStats``). At 3.6 GFLOP a row,
padding is device time."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("cnn.rows") or not c.get("cnn_rows"):
        return None
    return c["cnn.rows"] / c["cnn_rows"]
