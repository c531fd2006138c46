"""Device time per call of the collective operations (the column
sharding's halo exchange, ``ppermute``) on the busiest device,
milliseconds.  Read only where the cell runs on several chips."""


def read(ctx):
    if ctx["chips"] < 2 or not ctx["calls"]:
        return None
    r = ctx["reduction"]
    if not r["per_device"][r["busiest"]]["ops"]:
        return None  # the trace shows no device operation to read
    return r["per_device"][r["busiest"]]["collective_s"] / ctx["calls"] * 1e3
