"""Cheap-CNN rows per ingested object (``IngestStats``): what the pixel
tracker and the redundancy gate leave for the megastep."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("objects"):
        return None
    return c["cnn_rows"] / c["objects"]
