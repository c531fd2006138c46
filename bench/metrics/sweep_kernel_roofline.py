"""The sweep kernel's share of its roofline, in percent: the least time
the chip needs for the call's required work (``bench/work.py``: one read
of the input and one write of the output, two flops per non-zero tap per
point per application), over the kernel's device time per call.  The
least time is the larger of bytes over peak HBM bandwidth and flops over
the peak in ``peaks.json``; at about 3 flop/B against a ridge near 240
flop/B every cell is bound by the bytes.  On several chips each holds
its share of the work and the busiest device's kernel time counts."""


def read(ctx):
    r = ctx["reduction"]
    if not r["per_device"][r["busiest"]]["ops"]:
        return None  # the trace shows no device operation to read
    kernel = r["per_device"][r["busiest"]]["kernel_s"]
    if not kernel or not ctx["calls"]:
        return None
    w, peaks, chips = ctx["work"], ctx["peaks"], ctx["chips"]
    least = max(w["bytes"] / chips / peaks["hbm_bytes_per_s"],
                w["flops"] / chips / peaks["bf16_flops_per_s"])
    return least / (kernel / ctx["calls"]) * 100.0
