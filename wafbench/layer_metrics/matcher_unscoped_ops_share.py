"""Per-tier matcher executable: the share of a matcher launch's device
operations that stand under no ``cko.`` scope, over the matcher
executables the capture ran, weighted by their runs (``_device_ops.py``).
The instrumentation's own coverage: traced code added outside every
scope shows here."""

from wafbench.layer_metrics._device_ops import weighted

SOURCE = "program_counter"


def read(ctx):
    sums = weighted(ctx, lambda ops: ops["unscoped"])
    if sums is None or not sums[1]:
        return None
    return 100.0 * sums[0] / sums[1]
