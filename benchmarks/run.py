"""The paper's own figures and tables, one function each.

Prints ``name,us_per_call,derived`` CSV per benchmark.  ``--full`` runs
paper-scale sweeps; default is the quick pass.  These reproduce the
paper's simulated-cache claims; the on-chip benchmark of the engine is
``bench/`` (``BENCHMARK.json``).
"""
from __future__ import annotations

import sys

from .common import force_cpu_devices


def main() -> None:
    quick = "--full" not in sys.argv[1:]
    force_cpu_devices()
    from . import (
        bounds_table, fig4_miss_reduction, fig5_unfavorable, padding_effect,
        roofline_report,
    )
    fig4_miss_reduction.main(quick)
    fig5_unfavorable.main(quick)
    bounds_table.main(quick)
    padding_effect.main(quick)
    roofline_report.main(quick)


if __name__ == "__main__":
    main()
