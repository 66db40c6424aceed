"""Compile: seconds spent tracing and compiling (or loading from the
persistent cache) tier executables during set-up
(``compile_cache.tier_compile_s``: label -> cumulative seconds)."""

SOURCE = "program_span"


def read(ctx):
    tiers = ctx["setup"]["compile_cache"].get("tier_compile_s")
    return sum(tiers.values()) if tiers else None
