"""Host time of one call of the public entry, from the call until it
returns the unready array: program build, plan key, plan cache and jit
dispatch.  Mean over the traced window's calls, in microseconds (host
clock)."""


def read(ctx):
    d = ctx["dispatch_s"]
    return sum(d) / len(d) * 1e6 if d else None
