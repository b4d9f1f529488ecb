"""Share of the requests' service time spent inside the blocked GT call
(harness span ``gt_apply``); the rest is shard open, rank and frame
merge in the archive layer."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("service_s"):
        return None
    return c["gt_s"] / c["service_s"]
