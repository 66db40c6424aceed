"""Per-tier matcher executable: convolutions a matcher launch makes in
its conv tier: row chunks x column tiles of the plan the executable was
traced with (``/waf/v1/stats`` ``compile_cache.executables[].seg_plan``,
after warm-up: ``row_chunks`` x ``column_tiles``, a whole block counting
as one tile), of the matcher executables the capture ran, weighted by
their runs. A tier that fits its budget whole reads its block count; one
cut over rows and columns reads what the cut costs in passes of the
chains. An executable on the long DFA scan makes no convolution and is
left out (fewer steps are better, and the scan is the plan the cell exists
to rule out: ``seg_long_scan_launch_share`` speaks for it); where every
launch took it, and for a program that records no plan, there is nothing
to read. With its sister this is a witness of the PLAN, a constant of the
compile, not a timing: it moves when the plan does, never with load."""

from wafbench.layer_metrics._device_ops import executable_name
from wafbench.layer_metrics._trace_windows import POST_STAGE

SOURCE = "program_counter"


def read(ctx):
    listed = ctx["setup"].get("compile_cache", {}).get("executables")
    if not listed:
        return None
    runs: dict[str, int] = {}
    for module, n in ctx["trace"]["module_runs"].items():
        if POST_STAGE not in module:
            name = executable_name(module)
            runs[name] = runs.get(name, 0) + n
    steps = [(e["seg_plan"]["row_chunks"] * e["seg_plan"]["column_tiles"], runs[e["name"]])
             for e in listed
             if (e.get("seg_plan") or {"path": "long"})["path"] != "long" and runs.get(e["name"])]
    if not steps:
        return None
    return sum(s * n for s, n in steps) / sum(n for _s, n in steps)
