"""Per-tier matcher executable: device operations a matcher launch is
made of (fusions, copies, kernels, loops: every instruction of the
optimized HLO that a trace would show, a loop body once), of the matcher
executables the capture ran, weighted by their runs
(``_device_ops.py``). Thousands of small operations are what a chain
structure of its own per rule shape costs before any of them is timed."""

from wafbench.layer_metrics._device_ops import matcher_launches

SOURCE = "program_counter"


def read(ctx):
    launches = matcher_launches(ctx)
    if launches is None:
        return None
    return sum(ops["total"] * n for ops, n in launches) / sum(n for _ops, n in launches)
