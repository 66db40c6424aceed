#!/usr/bin/env python3
"""Build-time tool: CRS behind a gateway that keeps about a hundred
requests in flight, frozen into data files: ``crs-lite-pl2``'s rule text
under windows of about 500 unique rows.

    JAX_PLATFORMS=cpu CKO_NATIVE_LIB=build/wafbench/libcko_native.so \
        python3 -m wafbench.tools.freeze_ingress wafbench/configs/crs-lite-pl2-ingress

Imports what ``freeze_config.py`` has (its synthetic pools' tables) and
``freeze_bodies.py``'s ``materialize`` and edits neither. Reads
``freeze.json`` in the configuration's directory and writes

1. ``rules/``: ``rules_base``'s tree byte for byte;
2. ``corpus.jsonl``: ``pool_requests`` requests: the distinct requests
   ``pool_base``'s plan ``base_plan`` sends down its ``base_lane`` lane
   (go-ftw), then header-only ``GET`` requests from a copy of the
   program's ``corpus.synthetic_requests`` (``seed``, ``attack_ratio``, no
   bodies), each with the salt argument the go-ftw requests carry; every
   verdict the plain host evaluator's on the whole text on 4 salts, kept
   only where all agree;
3. ``plans/<plan>.json``: steady bursts that send every pool request once
   a pass, each the most requests, within ``burst_requests``, that keep it
   to ``miss_lo``-``miss_hi`` unique uncached rows and ``wire_bytes_max``
   bytes on the wire (one socket read, one window), go-ftw and synthetic
   in the pool's own proportion; and a
   prime pass from a cold value cache whose last group is the first steady
   burst. Every group is placed with the engine's own tensorizer and a
   replica of its value cache, and the steady bursts are held to one set of
   executables: the matcher on ``tier_shape``, the short tier's padding
   launch and one post stage;
4. ``frozen.json``: what came out.

A window of a hundred CRS requests holds thousands of short pair rows
(names, methods, hosts), so the engine's tiering gives them a tier of
their own beside the 512-byte one (``engine/waf.py:tier_tensors`` counts
pair rows, not unique ones, against ``_MIN_TIER_ROWS``). Steady, the value
cache holds every one of them and that tier's matcher runs one padding
row (``1x64``); cold, it is one more matcher shape, which the prime groups
are packed to keep to one bucket of rows (``prime_narrow_rows``):
``frozen.json`` states both (``second_matcher_shapes``).

Like its sisters it is never run by a benchmark run.
"""

from __future__ import annotations

import argparse
import base64
import itertools
import json
import os
import random
import shutil
import sys
from pathlib import Path

from wafbench.generators.planned_bursts import SALT_TOKEN, salt_for
from wafbench.harness import read_rules
from wafbench.tools.freeze_bodies import materialize
from wafbench.tools.freeze_config import (
    _ATTACK_QUERIES,
    _BENIGN_PATHS,
    _HOST_POOL,
    _UA_POOL,
)


def base_requests(pool_dir: Path, plan: str, lane: str) -> list[dict]:
    """The distinct pool requests ``plan`` sends down ``lane``, in pool
    order, each with the verdict the base's table holds."""
    pool = [json.loads(line) for line in open(pool_dir / "corpus.jsonl")]
    steady = json.loads((pool_dir / "plans" / f"{plan}.json").read_text())["steady"]
    sent = sorted({i for b in steady if b["lane"] == lane for i in b["requests"]})
    return [{"id": pool[i]["id"], "wire": base64.b64decode(pool[i]["wire"]),
             "want": (pool[i]["status"], pool[i]["rule_id"]), "declared": pool[i]["declared"]}
            for i in sent]


def synthetic_requests(n: int, spec: dict) -> list[dict]:
    """Copy of ``coraza_kubernetes_operator_tpu/corpus.py:synthetic_requests``
    (the same draws in the same order), cut to header-only ``GET``s: the
    request's own ``_r`` salt gives way to the pool's salt argument, the
    session cookie keeps its token (a visitor's cookie comes back with every
    request of theirs), and the 30% that would be a ``POST`` stay a ``GET``."""
    rng = random.Random(spec["seed"])
    salt = SALT_TOKEN.decode()
    out = []
    for i in range(n):
        attack = rng.random() < spec["attack_ratio"]
        token = f"{i:x}{rng.randrange(1 << 24):x}"
        base = rng.choice(_ATTACK_QUERIES if attack else _BENIGN_PATHS).replace(" ", "%20")
        uri = f"{base}{'&' if '?' in base else '?'}{spec['salt_arg']}={salt}"
        headers = [
            ("Host", rng.choice(_HOST_POOL)),
            ("User-Agent", rng.choice(_UA_POOL)),
            ("Accept", "*/*"),
            ("Cookie", f"session={token}{rng.randrange(1 << 28):07x}"),
        ]
        rng.random()  # the original's POST draw
        wire = f"GET {uri} HTTP/1.1\r\n" + "".join(f"{k}: {v}\r\n" for k, v in headers) + "\r\n"
        out.append({"id": f"syn-{i}", "wire": wire.encode(), "want": None, "declared": [],
                    "attack": attack})
    return out


def wire_bytes(wire: bytes, salt_hex: int) -> int:
    """A pool request's bytes on the wire, salted."""
    return len(wire) + wire.count(SALT_TOKEN) * (salt_hex - len(SALT_TOKEN))


def raw_pool(spec: dict, configs: Path, n: int) -> list[dict]:
    """``n`` requests before their verdicts: the base's go-ftw requests
    (a quarter of a slice; all of them in the whole pool), then synthetic
    ones up to ``n`` and ``pool_spare`` more to draw on where a verdict
    moves with the salt."""
    base = base_requests(configs / spec["pool_base"], spec["base_plan"], spec["base_lane"])
    if n < spec["pool_requests"]:
        base = base[: n // 4]
    return base + synthetic_requests(n - len(base) + spec["pool_spare"], spec)


def reference(engine, raw: list[dict], spec: dict, n: int, log=None) -> tuple[list[dict], dict]:
    """The pool with its reference verdicts: the plain host evaluator on
    ``salt_seeds`` + 1 salts, a request kept only where all agree (and a
    go-ftw request only where that is what the base's table holds)."""
    salt_hex = spec["salt_hex"]
    seeds = list(range(1, spec["salt_seeds"] + 1)) + [2**31 + 12345]
    by_seed = []
    for seed in seeds:
        reqs = [materialize(r["wire"], salt_for(seed, "freeze", i, salt_hex))
                for i, r in enumerate(raw)]
        by_seed.append([(v.status if v.interrupted else 200,
                         str(v.rule_id or 0) if v.interrupted else None)
                        for v in engine.host_fallback.evaluate(reqs)])
        if log:
            print(f"reference verdicts, salt seed {seed}: done", file=log)
    pool, left_out = [], {"salt_moves_verdict": 0, "spare_not_needed": 0}
    for i, r in enumerate(raw):
        got = {vs[i] for vs in by_seed}
        if len(pool) == n:
            left_out["spare_not_needed"] += 1
        elif len(got) != 1:
            left_out["salt_moves_verdict"] += 1
        elif r["want"] is not None and got != {tuple(r["want"])}:
            raise SystemExit(f"{r['id']}: the same text says {got}, the base's table {r['want']}")
        else:
            (status, rule_id), = got
            pool.append(dict(r, status=status, rule_id=rule_id))
    if len(pool) != n:
        raise SystemExit(f"{len(pool)} requests kept of {n}: raise pool_spare")
    return pool, dict(left_out, salt_seeds=seeds)


def corpus_line(r: dict) -> str:
    return json.dumps({"id": r["id"], "wire": base64.b64encode(r["wire"]).decode(),
                       "status": r["status"], "rule_id": r["rule_id"],
                       "declared": r["declared"]})


def interleave(pool: list[dict], seed: int) -> list[int]:
    """Pool indexes, drawn once: go-ftw and synthetic requests each
    shuffled, then dealt so that every stretch holds them in the pool's
    own proportion."""
    rng = random.Random(seed)
    ftw = [i for i, r in enumerate(pool) if "attack" not in r]
    syn = [i for i, r in enumerate(pool) if "attack" in r]
    rng.shuffle(ftw)
    rng.shuffle(syn)
    out, a, b = [], 0, 0
    while a < len(ftw) or b < len(syn):
        if b == len(syn) or (a < len(ftw) and a * len(syn) <= b * len(ftw)):
            out.append(ftw[a])
            a += 1
        else:
            out.append(syn[b])
            b += 1
    return out


def equal_cuts(order: list[int], k: int) -> list[list[int]]:
    """``order`` in ``k`` runs whose lengths differ by at most one."""
    n = len(order)
    edges = [round(j * n / k) for j in range(k + 1)]
    return [order[a:b] for a, b in zip(edges, edges[1:])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_dir", type=Path)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this tool must not take a chip")

    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.tier_compile import spec_key
    from coraza_kubernetes_operator_tpu.engine.value_cache import ValueHitCache
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine, warmup_request

    cdir = args.config_dir.resolve()
    spec = json.loads((cdir / "freeze.json").read_text())
    salt_hex = spec["salt_hex"]
    shutil.rmtree(cdir / "rules", ignore_errors=True)
    shutil.copytree((cdir.parent / spec["rules_base"]).resolve(), cdir / "rules")
    config = json.loads((cdir / "config.json").read_text())
    engine = WafEngine(read_rules(cdir / config["rules"]))
    if not engine._native.available:
        raise SystemExit("native tensorizer not loaded (set CKO_NATIVE_LIB): "
                         "the plan must be made by the tensorizer the sidecar uses")
    if engine.value_cache is None:
        raise SystemExit("value cache is a shipped default; engine has none")

    n_pool = spec["pool_requests"]
    pool, left_out = reference(engine, raw_pool(spec, cdir.parent, n_pool), spec, n_pool,
                               log=sys.stderr)

    # -- the plan ---------------------------------------------------------------------
    want_shape = spec["tier_shape"]
    lo, hi, byte_max = spec["miss_lo"], spec["miss_hi"], spec["wire_bytes_max"]
    narrow_lo, narrow_hi = spec["prime_narrow_rows"]
    req_lo, req_hi = spec["burst_requests"]
    narrow_width = spec["narrow_width"]
    serial = itertools.count(10**9)

    def place(idxs, keep: bool) -> dict:
        """One window of pool requests ``idxs`` on fresh salts: its tiers'
        shapes, the unique uncached rows, pair rows and cached rows of each,
        and the executables it launches (a tier with no row to match
        launches its matcher on one padding row); ``keep`` caches its rows as
        a served window does."""
        reqs = [materialize(pool[i]["wire"], salt_for(0, "freeze", next(serial), salt_hex))
                for i in idxs]
        tiers, numvals, masks, cached, miss_keys, lease = engine._batch_tensors(reqs)
        match_specs, post_spec, _pairs = engine._tier_specs(
            tiers, numvals, max_phase=2, masks=masks, cached=cached)
        if lease is not None:
            lease.release()
        if keep:
            for keys in miss_keys:
                if keys:
                    engine.value_cache.insert(
                        keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8))
        misses = [len(k) for k in miss_keys]
        return {"shapes": [list(t[0].shape) for t in tiers], "misses": misses,
                "post": [[int(t[5].shape[0]), int(c.shape[0])] for t, c in zip(tiers, cached)],
                "masks": [m is not None for m in masks],
                "executables": tuple(spec_key(s) for s in match_specs) + (spec_key(post_spec),),
                "requests": len(idxs),
                "wire_bytes": sum(wire_bytes(pool[i]["wire"], salt_hex) for i in idxs)}

    def cold() -> None:
        engine.value_cache = ValueHitCache(engine.value_cache.packed_len,
                                           engine.value_cache.max_bytes)
        place_warm = engine._batch_tensors([warmup_request()])
        if place_warm[5] is not None:
            place_warm[5].release()
        for keys in place_warm[4]:
            if keys:
                engine.value_cache.insert(
                    keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8))

    def wide_only(p: dict, rows_lo: int) -> bool:
        """A burst of ``burst_requests``: the narrow tier all cached (one
        padding row), the wide one ``rows_lo``-``hi`` rows on ``want_shape``,
        inside one socket read."""
        return (p["shapes"] == [[1, narrow_width], want_shape] and not any(p["masks"])
                and p["misses"][0] == 0 and rows_lo <= p["misses"][1] <= hi
                and p["requests"] <= req_hi and p["wire_bytes"] <= byte_max)

    order = interleave(pool, spec["seed"])

    # Steady: the fewest bursts of equal size that hold every pool request
    # once and stay inside the window's rows and the read's bytes, so each
    # is the most requests that do. Placed after a pass that has cached
    # every unsalted row, as the prime pass leaves it.
    cold()
    for group in equal_cuts(order, spec["prime_groups_max"]):
        place(group, keep=True)
    steady_groups = None
    for k in range(1, n_pool):
        groups = equal_cuts(order, k)
        if all(wide_only(place(g, keep=False), 0) for g in groups):
            steady_groups = groups
            break
    if steady_groups is None or min(len(g) for g in steady_groups) < req_lo:
        raise SystemExit("no cut of the pool into equal bursts fits the window")

    # Prime, from a cold cache: the fewest groups of equal size whose short
    # rows (cold here, a tier of their own) stay in one bucket and whose wide
    # rows fit the steady shape; then the first steady burst, every value of
    # it seen, which mints the steady post stage.
    def prime_pass(k: int):
        cold()
        out = []
        for g in equal_cuts(order, k):
            p = place(g, keep=True)
            ok = (len(p["shapes"]) == 2 and p["shapes"][1] == want_shape
                  and not any(p["masks"]) and narrow_lo <= p["misses"][0] <= narrow_hi
                  and want_shape[0] // 2 < p["misses"][1] <= hi and p["wire_bytes"] <= byte_max)
            if not ok:
                return None
            out.append((g, p))
        return out

    prime_groups = next((got for k in range(len(steady_groups), spec["prime_groups_max"] + 1)
                         if (got := prime_pass(k)) is not None), None)
    if prime_groups is None:
        raise SystemExit(f"no prime pass of at most {spec['prime_groups_max']} equal groups "
                         f"keeps the short rows to {narrow_lo}-{narrow_hi} a group")
    prime_groups.append((steady_groups[0], place(steady_groups[0], keep=True)))

    def entry(g, p) -> dict:
        return {"lane": "interactive", "requests": g, "unique_uncached_rows": p["misses"][-1],
                "tier_shapes": p["shapes"], "tier_rows": p["misses"], "post_shapes": p["post"],
                "wire_bytes": p["wire_bytes"]}

    steady, sigs = [], []
    for g in steady_groups:
        p, again = place(g, keep=True), place(g, keep=True)
        if not wide_only(p, lo) or (again["shapes"], again["misses"], again["executables"]) != (
                p["shapes"], p["misses"], p["executables"]):
            raise SystemExit(f"steady burst of {len(g)} lands on {p['shapes']} with "
                             f"{p['misses']} rows, {p['wire_bytes']} bytes, then {again['misses']}")
        sigs.append(p["executables"])
        steady.append(dict(entry(g, p), ftw_requests=sum("attack" not in pool[i] for i in g)))
    if len(set(sigs)) != 1 or len(sigs[0]) != 3:
        raise SystemExit(f"the steady bursts launch {len(set(sigs))} sets of executables: "
                         f"{sorted({str(b['post_shapes']) for b in steady})}")
    prime = [entry(g, p) for g, p in prime_groups]
    plan = {"tier_shapes": [want_shape], "prime": prime, "steady": steady,
            "requests_per_pass": sum(len(g) for g in steady_groups),
            "left_out": {"pool_requests_never_sent": n_pool - len({i for g in steady_groups
                                                                   for i in g})}}

    with open(cdir / "corpus.jsonl", "w") as fh:
        for r in pool:
            fh.write(corpus_line(r) + "\n")
    (cdir / "plans").mkdir(exist_ok=True)
    (cdir / "plans" / f"{spec['plan']}.json").write_text(json.dumps(plan) + "\n")

    syn = [r for r in pool if "attack" in r]
    auto = engine.automata_summary()
    narrow = sorted({tuple(b["tier_shapes"][0]) for b in prime[:-1]})
    summary = {
        "pool_requests": len(pool),
        "ftw_requests": len(pool) - len(syn),
        "synthetic_requests": len(syn),
        "blocked": sum(r["status"] != 200 for r in pool),
        "allowed": sum(r["status"] == 200 for r in pool),
        "ftw_blocked": sum(r["status"] != 200 for r in pool if "attack" not in r),
        "synthetic_attacks": sum(r["attack"] for r in syn),
        "synthetic_attacks_blocked": sum(r["attack"] and r["status"] != 200 for r in syn),
        "synthetic_benign_blocked": sum(not r["attack"] and r["status"] != 200 for r in syn),
        "synthetic_benign_blocked_by": {
            rid: sum(not r["attack"] and r["rule_id"] == rid for r in syn)
            for rid in sorted({r["rule_id"] for r in syn if not r["attack"] and r["rule_id"]})},
        "left_out": left_out,
        "rules_compiled": len(engine.rule_meta),
        "rules_skipped": len(engine.compiled.report.skipped),
        "automata_summary": {k: auto[k] for k in (
            "rules", "segment_columns", "segment_splits", "flat_bins", "flat_slots")},
        "plan": {
            "steady_bursts": len(steady), "requests_per_pass": plan["requests_per_pass"],
            "steady_requests": sorted({len(b["requests"]) for b in steady}),
            "steady_ftw_requests": sorted({b["ftw_requests"] for b in steady}),
            "steady_rows": sorted({b["unique_uncached_rows"] for b in steady}),
            "steady_wire_bytes": [min(b["wire_bytes"] for b in steady),
                                  max(b["wire_bytes"] for b in steady)],
            "steady_tier_shapes": sorted({str(b["tier_shapes"]) for b in steady}),
            "steady_post_shapes": sorted({str(b["post_shapes"]) for b in steady}),
            "steady_executable_sets": len(set(sigs)),
            "steady_matcher_launches_a_burst": len(sigs[0]) - 1,
            "steady_matcher_rows_a_burst": sorted({str(b["tier_rows"]) for b in steady}),
            "prime_groups": len(prime),
            "prime_requests": sorted({len(b["requests"]) for b in prime}),
            "prime_tier_rows": [b["tier_rows"] for b in prime],
            "prime_post_shapes": len({str(b["post_shapes"]) for b in prime}),
        },
        "second_matcher_shapes": {
            "prime": [list(s) for s in narrow],
            "steady": [[1, narrow_width]],
            "why": spec["second_matcher_shapes_why"],
            "cost": "two matcher executables more in set-up (traced, compiled or loaded); "
                    "inside the window the steady one runs one padding row a burst",
        },
    }
    (cdir / "frozen.json").write_text(json.dumps(summary, indent=1) + "\n")
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
