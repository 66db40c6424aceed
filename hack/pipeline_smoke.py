#!/usr/bin/env python
"""Host/device overlap smoke (ISSUE 4 CI satellite).

Runs the same batch stream twice through ``WafEngine.prepare`` /
``collect`` on the CPU backend — once strictly alternating (collect
window i before preparing window i+1: the pre-pipeline serial loop) and
once double-buffered (window i+1's host assembly overlaps window i's
XLA compute, bounded in-flight depth) — and asserts:

1. pipelined throughput >= RATIO x the sync path (default 1.2: XLA:CPU
   executes on its own thread pool with the GIL released, so host
   tensorize/tier work genuinely overlaps device compute on a multicore
   runner), and
2. the two passes' verdicts are BIT-IDENTICAL (pipelining is a pure
   scheduling change — it must never alter a verdict).

The workload is sized so host assemble and device step are comparable
(that is where double buffering pays; a degenerate stage ratio measures
nothing). The measurement discipline (untimed warm, value-cache bypass
for shape stability, deque double buffer) is the shared
``testing/overlap.py`` helper.

Usage: pipeline_smoke.py [--ratio 1.2] [--batches 12] [--batch 512]
(env overrides: PIPELINE_SMOKE_RATIO / _BATCHES / _BATCH). Exit 0 on
pass; 1 with a JSON diagnostic line on fail.
"""

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def main() -> int:
    ratio_env = os.environ.get("PIPELINE_SMOKE_RATIO")
    ratio = float(ratio_env) if ratio_env else 1.2
    ratio_explicit = ratio_env is not None
    n_batches = int(os.environ.get("PIPELINE_SMOKE_BATCHES", "12"))
    batch = int(os.environ.get("PIPELINE_SMOKE_BATCH", "512"))
    depth = int(os.environ.get("CKO_PIPELINE_DEPTH", "2"))
    args = sys.argv[1:]
    while args:
        a = args.pop(0)
        if a == "--ratio":
            ratio = float(args.pop(0))
            ratio_explicit = True
        elif a == "--batches":
            n_batches = int(args.pop(0))
        elif a == "--batch":
            batch = int(args.pop(0))
    single_core = (os.cpu_count() or 1) <= 1
    if single_core and not ratio_explicit:
        # One core = no concurrency to overlap: host assembly and XLA
        # compute timeshare and the ideal speedup is 1.0. The gate
        # degrades (loudly) to "no regression + bit-identical verdicts";
        # CI runners are multicore and keep the strict 1.2x bar.
        ratio = 0.9

    sys.path.insert(0, str(REPO))
    import jax

    jax.config.update("jax_platforms", "cpu")

    from coraza_kubernetes_operator_tpu.corpus import (
        synthetic_crs,
        synthetic_requests,
    )
    from coraza_kubernetes_operator_tpu.engine.compile_cache import (
        configure_persistent_cache,
    )
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.testing.overlap import (
        measure_overlap,
        verdict_tuple,
    )

    configure_persistent_cache(os.environ.get("CKO_COMPILE_CACHE_DIR"))
    eng = WafEngine(synthetic_crs(40, seed=3))
    batches = [
        synthetic_requests(batch, attack_ratio=0.2, seed=100 + i)
        for i in range(n_batches)
    ]
    m = measure_overlap(eng, batches, depth=depth)
    sync_wall, pipe_wall = m["sync_wall"], m["pipe_wall"]
    host_s, device_s, decode_s = m["host_s"], m["device_s"], m["decode_s"]

    identical = all(
        [verdict_tuple(a) for a in sv] == [verdict_tuple(b) for b in pv]
        for sv, pv in zip(m["sync_verdicts"], m["pipe_verdicts"])
    )
    blocked = sum(v.interrupted for vs in m["sync_verdicts"] for v in vs)
    speedup = sync_wall / max(pipe_wall, 1e-9)
    n_req = batch * n_batches
    verdict = {
        "req_per_s_sync": round(n_req / sync_wall, 1),
        "req_per_s_pipelined": round(n_req / pipe_wall, 1),
        "speedup": round(speedup, 3),
        "required": ratio,
        "depth": depth,
        "batches": n_batches,
        "batch": batch,
        "stage_s": {
            "host_assemble": round(host_s / n_batches, 4),
            "device_step": round(device_s / n_batches, 4),
            "decode": round(decode_s / n_batches, 5),
        },
        "verdicts_identical": identical,
        "blocked": blocked,
        # Must be misses == 0: a compile paid inside either timed pass
        # voids the comparison (a sync-pass miss fakes the speedup, a
        # pipelined-pass miss fakes a regression).
        "compile_cache": m["compile_cache"],
        "cpus": os.cpu_count(),
        "single_core_degraded_gate": single_core and not ratio_explicit,
    }
    ok = (
        speedup >= ratio
        and identical
        and blocked > 0
        and m["compile_cache"]["misses"] == 0
    )
    verdict["smoke"] = "PASS" if ok else "FAIL"
    print(json.dumps(verdict))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
