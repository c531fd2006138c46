"""Device time per call of every operation that is neither a Mosaic
kernel nor a collective: the launch buffer's pad or embed, the periodic
wrap fill, the trim.  Busiest device, milliseconds.  0 where the call
runs no such operation."""


def read(ctx):
    r = ctx["reduction"]
    if not r["per_device"][r["busiest"]]["ops"]:
        return None  # the trace shows no device operation to read
    if not ctx["calls"]:
        return None
    return r["per_device"][r["busiest"]]["xla_s"] / ctx["calls"] * 1e3
