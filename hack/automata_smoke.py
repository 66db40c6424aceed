#!/usr/bin/env python
"""Two-level automata smoke (ISSUE 18 CI satellite).

Replays the ftw corpus (attack stages + synthetic benign fill) against
the crs-lite ruleset through TWO engines built from ONE compiled
ruleset:

1. two-level automata OFF (``CKO_AUTOMATA=0``) — the exact pre-feature
   layout: every group on segment/NFA banks; then
2. two-level automata ON — DFA-hot groups and the big groups'
   approximate prefilters (with host confirm) are scanned in the fused
   flat-slot bins, with every other dense-DFA block (no block of
   crs-lite is outside them).

Gates (exit 1 with the JSON diagnostic on any failure):

- verdicts BYTE-IDENTICAL per request between the two engines
  (status + interrupted + rule id + matched rule ids);
- the plan exercised the new tiers: >= 1 DFA-hot group and >= 1
  prefiltered group on crs-lite, a dense block of each in the model,
  every dense block covered by a flat bin (``per_bank_kernels`` 0 in
  ``automata_summary()``), and prefilter rows actually examined by the
  confirm step;
- Pallas interpret-mode parity on CPU (``interpret=True``, the exact
  TPU kernel program): every flat bin's kernel output equals its XLA
  twin on a live batch.

Usage: automata_smoke.py [--requests 384] [--batch 128]
(env overrides: AUTOMATA_SMOKE_REQUESTS / AUTOMATA_SMOKE_BATCH).
"""

import argparse
import json
import os
import random
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _fail(diag: dict, why: str) -> None:
    diag["pass"] = False
    diag["fail_reason"] = why
    print(json.dumps(diag))
    sys.exit(1)


def _verdict_key(v):
    return (v.status, v.interrupted, v.rule_id, tuple(v.matched_ids))


def _ftw_replay(n: int):
    """ftw attack stages interleaved with synthetic benign traffic,
    sized for a smoke."""
    from coraza_kubernetes_operator_tpu.corpus import synthetic_requests
    from coraza_kubernetes_operator_tpu.ftw.loader import load_tests
    from coraza_kubernetes_operator_tpu.ftw.runner import _stage_request

    attacks = [
        _stage_request(s)
        for t in load_tests(REPO / "ftw" / "tests-crs-lite")
        for s in t.stages
    ]
    benign = synthetic_requests(n, attack_ratio=0.0, seed=1)
    rng = random.Random(1)
    return [
        attacks[i % len(attacks)] if rng.random() < 0.4 else benign[i]
        for i in range(n)
    ]


def _interpret_parity(engine, diag: dict) -> None:
    """Every flat bin: interpret-mode Pallas kernel output == its XLA
    twin on a live random batch."""
    import numpy as np

    from coraza_kubernetes_operator_tpu.ops.dfa_flat import (
        scan_flat_bank,
        scan_flat_xla,
    )

    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(64, 96), dtype=np.uint8)
    lengths = rng.integers(0, 97, size=(64,)).astype(np.int32)
    for fi, flat in enumerate(engine.model.flat_banks):
        sub = {p: (data, lengths) for p in set(flat.seg_pipes)}
        ref = np.asarray(scan_flat_xla(flat, sub))
        got = np.asarray(scan_flat_bank(flat, sub, interpret=True))
        if not (got == ref).all():
            _fail(diag, f"interpret-mode kernel diverged on flat bin {fi}")
    diag["interpret_parity_flat_bins"] = len(engine.model.flat_banks)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--requests",
        type=int,
        default=int(os.environ.get("AUTOMATA_SMOKE_REQUESTS", "384")),
    )
    ap.add_argument(
        "--batch",
        type=int,
        default=int(os.environ.get("AUTOMATA_SMOKE_BATCH", "128")),
    )
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(REPO))

    from coraza_kubernetes_operator_tpu.engine.compile_cache import (
        configure_persistent_cache,
    )

    # Warm-start XLA compiles across runs ($CKO_COMPILE_CACHE_DIR, else
    # a repo-local default shared with the test suite) — the CI job's
    # actions/cache step keys on this directory.
    configure_persistent_cache(
        os.environ.get("CKO_COMPILE_CACHE_DIR") or str(REPO / "tests" / ".jax_cache")
    )

    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text

    diag: dict = {"smoke": "automata", "requests": args.requests}
    t0 = time.monotonic()
    crs = compile_rules(load_ruleset_text())
    reqs = _ftw_replay(args.requests)
    diag["compile_s"] = round(time.monotonic() - t0, 1)

    os.environ["CKO_AUTOMATA"] = "0"
    eng_off = WafEngine(crs)
    os.environ["CKO_AUTOMATA"] = "1"
    eng_on = WafEngine(crs)

    counts = eng_on.automata_plan.counts()
    diag["tiers"] = counts
    summary = eng_on.automata_summary()
    for k in ("dfa_hot_blocks", "prefilter_blocks", "flat_bins", "flat_slots",
              "flat_groups", "per_bank_kernels"):
        diag[k] = summary[k]
    if counts["dfa-hot"] < 1:
        _fail(diag, "no DFA-hot group on crs-lite")
    if counts["prefiltered"] < 1:
        _fail(diag, "no prefiltered group on crs-lite")
    if not summary["dfa_hot_blocks"] or not summary["prefilter_blocks"]:
        _fail(diag, "automata tiers planned but no dense block built for them")
    if summary["per_bank_kernels"]:
        _fail(diag, "a dense-DFA block of crs-lite is not covered by a flat bin")

    _interpret_parity(eng_on, diag)

    t0 = time.monotonic()
    mismatches = 0
    for lo in range(0, len(reqs), args.batch):
        chunk = reqs[lo : lo + args.batch]
        v_off = eng_off.evaluate(chunk)
        v_on = eng_on.evaluate(chunk)
        for a, b in zip(v_off, v_on):
            if _verdict_key(a) != _verdict_key(b):
                mismatches += 1
    diag["replay_s"] = round(time.monotonic() - t0, 1)
    diag["mismatches"] = mismatches
    diag["prefilter"] = dict(eng_on.prefilter_stats)
    if mismatches:
        _fail(diag, f"{mismatches} verdict mismatches automata on vs off")
    if diag["prefilter"]["rows"] == 0:
        _fail(diag, "prefilter confirm step never examined a device row")

    diag["pass"] = True
    print(json.dumps(diag))


if __name__ == "__main__":
    main()
