"""Device dispatches per CNN batch of the sharded megastep
(``PipelineStats``): the megastep, plus the unmatched tail when a batch
opens clusters."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("batches"):
        return None
    return c["dispatches"] / c["batches"]
