#!/usr/bin/env python
"""Run a slice of the crs-lite conformance corpus in THIS process and
print one JSON summary line.

Why a chunk runner exists: jaxlib 0.9.0's XLA:CPU backend corrupts its
own process state after many successive compiles — the same response-
phase executable that compiles+serializes cleanly in a fresh process
(repro: round 4) segfaults in compile or in ``executable.serialize()``
once a few hundred compiles have accumulated (the full-suite crash
signature of rounds 3-4). The conformance tier is the biggest single
source of fresh compiles, so the pytest test shells the corpus out to
sequential chunk processes: each child performs only its slice's
compiles (warm entries come from the shared disk cache), writes new
entries, and exits before the backend degrades.

Usage: run_ftw_chunk.py START COUNT [CRS_PICKLE] [STRIDE]
(test indexes after title-sort; CRS_PICKLE skips the ~30s compile_rules
host work by loading the parent's pickled CompiledRuleSet; STRIDE > 1
selects every STRIDE-th test from START — the smoke-subset mode, which
keeps one RESIDENT child amortizing the ~3 min of jit tracing the
CRS-scale model costs per process over many tests)
"""

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

# Same JAX bootstrap as tests/conftest.py (children do not inherit it).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)
# Shared persistent compile cache (ISSUE 2): CKO_FTW_CACHE keeps its
# legacy priority, then the process-wide CKO_COMPILE_CACHE_DIR (the same
# dir the sidecar and CI use — chunk children then warm-start their
# XLA compiles from whatever any sibling already paid for), then the
# tests-local default.
_cache_dir = (
    os.environ.get("CKO_FTW_CACHE")
    or os.environ.get("CKO_COMPILE_CACHE_DIR")
    or str(REPO / "tests" / ".jax_cache")
)

from coraza_kubernetes_operator_tpu.engine.compile_cache import (  # noqa: E402
    configure_persistent_cache,
)

configure_persistent_cache(_cache_dir)


def main() -> None:
    start = int(sys.argv[1])
    count = int(sys.argv[2])
    crs_pickle = sys.argv[3] if len(sys.argv) > 3 else None
    stride = int(sys.argv[4]) if len(sys.argv) > 4 else 1
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text
    from coraza_kubernetes_operator_tpu.ftw.loader import load_overrides, load_tests_report
    from coraza_kubernetes_operator_tpu.ftw.runner import FtwRunner

    corpus = REPO / "ftw" / "tests-crs-lite"
    tests, skipped = load_tests_report(corpus)
    tests.sort(key=lambda t: t.title)
    chunk = tests[start : start + count * stride : stride]

    if crs_pickle:
        import pickle

        with open(crs_pickle, "rb") as f:
            crs = pickle.load(f)
    else:
        # Standalone invocation: reuse the persistent compiled-ruleset
        # cache (keyed by ruleset + compiler hash) instead of paying the
        # ~30s compile per chunk.
        from coraza_kubernetes_operator_tpu.compiler.ruleset import (
            compile_rules_cached,
        )

        crs = compile_rules_cached(
            load_ruleset_text(),
            cache_dir=str(REPO / "tests" / ".crs_cache"),
        )
    # The known-failure ledger is load-bearing in the GATING tier too
    # (VERDICT r4: the reference's ftw.yml is never decorative —
    # /root/reference/ftw/ftw.yml drives the replayed run).
    overrides = load_overrides(REPO / "ftw" / "ftw.yml")
    runner = FtwRunner(engine=WafEngine(crs), overrides=overrides)
    result = runner.run(chunk)
    print(
        json.dumps(
            {
                "total_tests": len(tests),
                "skipped_files": len(skipped),
                "passed": result.passed,
                "failed": result.failed,
                "ignored": result.ignored,
            }
        )
    )


if __name__ == "__main__":
    main()
