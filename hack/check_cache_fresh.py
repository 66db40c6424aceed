#!/usr/bin/env python
"""Fail when an XLA persistent cache is STALE relative to the code that
shapes the compiled HLO.

A warm cache is only warm for the code it was filled from: any
HLO-shaping source newer than the newest cache entry means the warm
pass (for ``tests/.jax_cache``: ``make test``) must be re-run LAST.
``make presubmit`` enforces that ordering.

Usage: check_cache_fresh.py CACHE_DIR [--hint 'make test']
Exit 0 = fresh; 1 = stale (a missing or empty cache dir is
stale by definition — the warm pass never ran).
"""

import argparse
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
# Directories whose .py sources shape traced HLO (compiler output,
# model layout, kernels, tiering). Controlplane/sidecar/host-only code
# does not invalidate compiled executables.
HLO_SHAPING = [
    "coraza_kubernetes_operator_tpu/models",
    "coraza_kubernetes_operator_tpu/ops",
    "coraza_kubernetes_operator_tpu/compiler",
    "coraza_kubernetes_operator_tpu/engine",
    "coraza_kubernetes_operator_tpu/parallel",
]


def newest_source_mtime() -> tuple[float, Path | None]:
    newest, who = 0.0, None
    for d in HLO_SHAPING:
        for p in (REPO / d).rglob("*.py"):
            m = p.stat().st_mtime
            if m > newest:
                newest, who = m, p
    return newest, who


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cache_dir")
    ap.add_argument("--hint", default="re-run the warm pass")
    args = ap.parse_args()
    cache = Path(args.cache_dir)
    if not cache.is_absolute():
        cache = REPO / cache

    src_mtime, src = newest_source_mtime()
    entries = list(cache.glob("*")) if cache.is_dir() else []
    if not entries:
        print(f"STALE: {cache} is empty — {args.hint}")
        return 1
    cache_mtime = max(p.stat().st_mtime for p in entries)
    if src_mtime > cache_mtime:
        print(
            f"STALE: {src} is newer than the newest entry in {cache} "
            f"(+{src_mtime - cache_mtime:.0f}s) — {args.hint}"
        )
        return 1
    print(f"fresh: {cache} ({len(entries)} entries) postdates all HLO-shaping sources")
    return 0


if __name__ == "__main__":
    sys.exit(main())
