"""Device time per call of the Mosaic kernels (``tpu_custom_call``
operations: the sweep engine's ``_sweep_kernel``).  Busiest device,
milliseconds."""


def read(ctx):
    r = ctx["reduction"]
    if not r["per_device"][r["busiest"]]["ops"]:
        return None  # the trace shows no device operation to read
    kernel = r["per_device"][r["busiest"]]["kernel_s"]
    if not kernel or not ctx["calls"]:
        return None
    return kernel / ctx["calls"] * 1e3
