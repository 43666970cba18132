"""Host time per step spent taking the next batch from the feed and
putting it on the device: the ``data`` span of the benchmark's loop."""


def read(ctx):
    data = [end - start for name, start, end in ctx.spans if name == "data"]
    return 1e3 * sum(data) / ctx.steps if ctx.steps else None
