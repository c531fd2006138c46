"""The work a call requires, from a configuration's shapes alone.

These counts are the yardstick of the roofline shares: they do not
depend on how the engine tiles, pads, fuses or shards the call, so a
change to the engine cannot change them.

* bytes: one read of the input and one write of the output at the
  configuration's dtype, per call, however many applications the call
  fuses;
* flops: a multiply and an add per tap of non-zero weight, per grid
  point, per application.
"""

from __future__ import annotations

from math import prod

__all__ = ["call_work", "points"]

ITEMSIZE = {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2,
            "int8": 1}


def points(config: dict) -> int:
    """Grid points of the configuration."""
    return prod(int(n) for n in config["grid"])


def call_work(config: dict, traffic: dict) -> dict:
    """``{"points", "applications", "bytes", "flops"}`` of one call."""
    n = points(config)
    apps = int(traffic["applications"])
    itemsize = ITEMSIZE[config["dtype"]]
    taps = sum(1 for w in config["operator"]["weights"] if float(w) != 0.0)
    return {
        "points": n,
        "applications": apps,
        "bytes": 2 * n * itemsize,
        "flops": 2 * taps * n * apps,
    }
