"""Share of the window the ingest loop waited on the generator (the
harness's ``source_wait`` span)."""


def read(ctx):
    c = ctx["counters"]
    if "source_wait_s" not in c:
        return None
    return c["source_wait_s"] / ctx["seconds"]
