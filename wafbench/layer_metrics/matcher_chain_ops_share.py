"""Per-tier matcher executable: the share of a matcher launch's device
operations that stand under the chain scopes (``cko.seg.bucket``,
``cko.seg.suffix``, ``cko.seg.final``: the gap chains behind the conv),
over the matcher executables the capture ran, weighted by their runs
(``_device_ops.py``)."""

from wafbench.layer_metrics._device_ops import CHAIN_SCOPES, weighted

SOURCE = "program_counter"


def read(ctx):
    sums = weighted(ctx, lambda ops: sum(ops["by_scope"].get(s, 0) for s in CHAIN_SCOPES))
    if sums is None or not sums[1]:
        return None
    return 100.0 * sums[0] / sums[1]
