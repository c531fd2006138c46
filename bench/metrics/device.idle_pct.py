"""The device's idle share of the traced window, in percent: one minus
the union of the intervals in which an operation ran, over the window
(host clock, first dispatch to last ready), averaged over the chips."""


def read(ctx):
    r = ctx["reduction"]
    if not r["window_s"] or not r["busy_s"]:
        return None
    return (1.0 - r["busy_s"] / r["window_s"]) * 100.0
