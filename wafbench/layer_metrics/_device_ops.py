"""What the readers of the executables' operation counts share (not a
metric): the matcher executables the capture ran, each with what one of
its launches is made of. ``/waf/v1/stats`` ``compile_cache.executables``
(after warm-up) holds, per resident ``cko_*`` executable, ``device_ops``:
``{"total", "by_scope": {scope: n}, "unscoped"}``, counted once from its
optimized HLO where it was compiled (the program's
``observability/device_scopes.py``). An executable run is joined to its
entry by name (the trace's ``jit_cko_match_32x512(<id>)`` is
``cko_match_32x512``); every executable but the post stage's is a
matcher. A program without the block gives nothing to read."""

from wafbench.layer_metrics._trace_windows import POST_STAGE

CHAIN_SCOPES = ("cko.seg.bucket", "cko.seg.suffix", "cko.seg.final")


def executable_name(module: str) -> str:
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def matcher_launches(ctx) -> list[tuple[dict, int]] | None:
    """(``device_ops``, runs in the capture) of every matcher executable
    that ran and was counted; None where the program keeps no counts or
    none of those that ran has one. Two models' executables of one name
    (tenants) each get the name's runs: the sums then weigh them alike."""
    listed = ctx["setup"].get("compile_cache", {}).get("executables")
    if not listed:
        return None
    runs: dict[str, int] = {}
    for module, n in ctx["trace"]["module_runs"].items():
        if POST_STAGE not in module:
            name = executable_name(module)
            runs[name] = runs.get(name, 0) + n
    out = [(e["device_ops"], runs[e["name"]]) for e in listed
           if e.get("device_ops") and runs.get(e["name"])]
    return out or None


def weighted(ctx, of) -> tuple[float, float] | None:
    """(sum over launches of ``of(device_ops)``, sum of ``total``), both
    weighted by runs."""
    launches = matcher_launches(ctx)
    if launches is None:
        return None
    return (sum(of(ops) * n for ops, n in launches),
            sum(ops["total"] * n for ops, n in launches))
