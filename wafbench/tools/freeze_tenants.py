#!/usr/bin/env python3
"""Build-time tool: freeze a deployment of many namespaced RuleSets on
one sidecar: the request pool with each request's tenant, the reference
verdict of every request ON ITS OWN TENANT'S RULE TEXT, and a burst plan
whose every burst has one fixed composition by rule text.

    JAX_PLATFORMS=cpu CKO_NATIVE_LIB=<libcko_native.so> \\
        python -m wafbench.tools.freeze_tenants wafbench/configs/<name>

Like ``freeze_config`` it imports the program's engine, is never run by
a benchmark run, and its outputs (``corpus.jsonl``, ``plans/*.json``,
``frozen.json``) are committed as data. It reads ``config.json`` (the
``instances``: which rule text each tenant deploys) and ``freeze.json``
(the pool, and under ``tenants`` the skew, the seed and a burst's
composition) and

1. rebuilds the pool of the configuration named by ``pool_copy_of``
   (checked byte for byte against that configuration's ``corpus.jsonl``)
   and cuts it into bursts of one lane each;
2. gives every burst the same number of requests per rule text (the
   Zipf expectation of a burst's draws, to within one request) at
   seeded positions,
   and inside a text's share draws each request's tenant from the
   conditional Zipf; the tenant goes into the request as an
   ``X-Waf-Tenant`` header, which the rules see like any other header;
3. computes each request's reference verdict with the plain host
   evaluator of its tenant's rule text, on ``salt_seeds`` + 1 salts, and
   refuses a pool in which a salt moves a verdict; counts the requests
   whose verdict differs from what the FIRST text would give them (a
   request routed to the wrong engine fails ``correct`` on those);
4. records with each text's engine, its tensorizer and a replica of its
   value cache the shape set of every burst's window of every text,
   cold (``prime``) and steady, checks that a replay of the steady
   bursts on fresh salts lands on the same ones, and lists per lane and
   text the shape sets the steady windows use (``tier_shapes``).
"""

from __future__ import annotations

import argparse
import base64
import itertools
import json
import os
import random
import sys
from pathlib import Path

from wafbench.harness import read_rules
from wafbench.tools.freeze_bodies import materialize
from wafbench.tools.freeze_config import _synthetic_wire, salt_for, synthetic_pool
from wafbench.tools.freeze_zipf import remember

LANES = ("interactive", "bulk")


def lane_of(wire: bytes) -> str:
    head, _, body = wire.partition(b"\r\n\r\n")
    return "bulk" if body and not head.startswith(b"GET ") else "interactive"


def with_header(wire: bytes, name: str, value: str) -> bytes:
    """The request with one more header, straight after ``Host``."""
    head, sep, rest = wire.partition(b"\r\n\r\n")
    lines = head.split(b"\r\n")
    if not lines[1].lower().startswith(b"host:"):
        raise SystemExit("pool request without a leading Host header")
    lines.insert(2, f"{name}: {value}".encode())
    return b"\r\n".join(lines) + sep + rest


def zipf_weights(n: int, s: float) -> list[float]:
    return [1.0 / (r ** s) for r in range(1, n + 1)]


def cut_bursts(wires: list[bytes], spec: dict, rng: random.Random):
    """(bursts, text of each pool request): a lane's requests in pool
    order, ``burst_requests`` at a time; every burst holds
    ``composition[t]`` requests of text ``t`` at seeded positions. A
    lane's last burst is filled up, text by text, with requests the lane
    has already sent."""
    size, comp = spec["burst_requests"], spec["composition"]
    if sum(comp) != size:
        raise SystemExit(f"composition {comp} does not add up to {size}")
    text_of: dict[int, int] = {}
    bursts = []
    for lane in LANES:
        mine = [i for i, w in enumerate(wires) if lane_of(w) == lane]
        for at in range(0, len(mine), size):
            fresh = mine[at:at + size]
            slots = [t for t, n in enumerate(comp) for _ in range(n)]
            rng.shuffle(slots)
            for i, t in zip(fresh, slots):
                text_of[i] = t
            requests = list(fresh)
            if len(fresh) < size:
                have = [sum(1 for i in fresh if text_of[i] == t) for t in range(len(comp))]
                earlier = mine[:at]
                for t, n in enumerate(comp):
                    pool_t = [i for i in earlier if text_of[i] == t]
                    requests += rng.sample(pool_t, n - have[t])
                tail = requests[len(fresh):]
                rng.shuffle(tail)
                requests = fresh + tail
            bursts.append({"lane": lane, "requests": requests})
    return bursts, text_of


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_dir", type=Path)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this tool must not take a chip")

    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine, warmup_request

    repo = Path(__file__).resolve().parents[2]
    cdir = args.config_dir.resolve()
    config = json.loads((cdir / "config.json").read_text())
    spec = json.loads((cdir / "freeze.json").read_text())
    tspec = spec["tenants"]
    salt_hex = spec["salt_hex"]

    # -- the deployment: tenants by rank, their rule texts, one engine a text -----
    instances = config["instances"]
    if len(instances) != tspec["count"]:
        raise SystemExit(f"{len(instances)} instances, freeze.json says {tspec['count']}")
    texts: list[str] = []  # distinct rule texts, in the order first deployed
    text_of_rank = []
    for inst in instances:
        text = read_rules(cdir / inst["rules"])
        if text not in texts:
            texts.append(text)
        text_of_rank.append(texts.index(text))
    n_texts = len(texts)
    want = [(r % n_texts) for r in range(len(instances))]
    if text_of_rank != want or n_texts != len(tspec["composition"]):
        raise SystemExit("the tenant of rank r must deploy text (r - 1) mod the number of texts")
    weights = zipf_weights(len(instances), tspec["zipf_s"])
    share = [sum(w for r, w in enumerate(weights) if text_of_rank[r] == t) / sum(weights)
             for t in range(n_texts)]
    expected = [tspec["burst_requests"] * s for s in share]
    if any(abs(x - n) >= 1.0 for x, n in zip(expected, tspec["composition"])):
        raise SystemExit(f"composition {tspec['composition']} is not within one request of "
                         f"the Zipf expectation {expected}")
    engines = [WafEngine(t) for t in texts]
    for e in engines:
        if not e._native.available:
            raise SystemExit("native tensorizer not loaded (set CKO_NATIVE_LIB): the plan "
                             "must be made by the tensorizer the sidecar uses")
        if e.value_cache is None:
            raise SystemExit("value cache is a shipped default; engine has none")

    # -- the pool: a copy of another configuration's, request for request ----------
    raw, _ = synthetic_pool(spec, repo)
    wires = [_synthetic_wire(req, salt_hex) for _rid, req, _st in raw]
    with open(cdir.parent / spec["pool_copy_of"] / "corpus.jsonl") as fh:
        theirs = [base64.b64decode(json.loads(line)["wire"]) for line in fh]
    if theirs != wires:
        raise SystemExit(f"the pool is not {spec['pool_copy_of']}'s")

    # -- bursts of one composition; tenants by the conditional Zipf ------------------
    rng = random.Random(tspec["seed"])
    bursts, text_of = cut_bursts(wires, tspec, rng)
    ranks_of_text = [[r for r in range(len(instances)) if text_of_rank[r] == t]
                     for t in range(n_texts)]
    rank_of: dict[int, int] = {}
    for i in sorted(text_of):
        ranks = ranks_of_text[text_of[i]]
        rank_of[i] = rng.choices(ranks, [weights[r] for r in ranks])[0]
    for b in bursts:
        named = {rank_of[i] for i in b["requests"]}
        if len(named) < tspec["min_tenants_per_burst"]:
            raise SystemExit(f"a burst names {len(named)} tenants: another seed")
        b["tenants"] = len(named)
    pool = [with_header(w, tspec["header"], instances[rank_of[i]]["instance"])
            for i, w in enumerate(wires)]

    # -- reference verdicts: each request on its own tenant's text --------------------
    def verdicts(engine, idxs, seed):
        reqs = [materialize(pool[i], salt_for(seed, i, salt_hex)) for i in idxs]
        return [(v.status if v.interrupted else 200,
                 str(v.rule_id or 0) if v.interrupted else None)
                for v in engine.host_fallback.evaluate(reqs)]

    seeds = list(range(1, spec["salt_seeds"] + 1)) + [2**31 + 12345]
    reference: dict[int, tuple] = {}
    for t, engine in enumerate(engines):
        idxs = [i for i in range(len(pool)) if text_of[i] == t]
        seen = [verdicts(engine, idxs, seed) for seed in seeds]
        for k, i in enumerate(idxs):
            got = {vs[k] for vs in seen}
            if len(got) != 1:
                raise SystemExit(f"request {i}: a salt moves its verdict on text {t}: {got}")
            reference[i] = got.pop()
        print(f"reference verdicts, text {t}: {len(idxs)} requests, {len(seeds)} salts",
              file=sys.stderr)
    on_first = verdicts(engines[0], list(range(len(pool))), seeds[0])
    depends = [i for i in range(len(pool)) if reference[i] != on_first[i]]
    depends_by_text = [sum(1 for i in depends if text_of[i] == t) for t in range(n_texts)]
    if len(depends) < tspec["min_tenant_dependent"]:
        raise SystemExit(f"only {len(depends)} verdicts depend on the tenant")

    # -- the plan: one window per text and burst, on one shape set a lane and text ----
    serial = itertools.count(10**9)

    def probe(engine, reqs):
        """(tier shapes, unique uncached rows of tier 0) of one window,
        its misses then remembered as the engine's value cache would."""
        tiers, _nv, masks, _cached, miss_keys, lease = engine._batch_tensors(reqs)
        if lease is not None:
            lease.release()
        remember(engine, miss_keys)
        if any(m is not None for m in masks):
            raise SystemExit("a masked tier: not a shape this plan can state")
        return [list(t[0].shape) for t in tiers], len(miss_keys[0])

    for engine in engines:
        probe(engine, [warmup_request()])  # the promotion probe's canary

    names = tspec["texts"]

    def replay():
        out = []
        for b in bursts:
            windows = {}
            for t, engine in enumerate(engines):
                idxs = [i for i in b["requests"] if text_of[i] == t]
                shapes, rows = probe(engine, [
                    materialize(pool[i], salt_for(0, next(serial), salt_hex)) for i in idxs])
                windows[names[t]] = {"requests": len(idxs), "unique_uncached_rows": rows,
                                     "tier_shapes": shapes}
            out.append({"lane": b["lane"], "requests": b["requests"], "tenants": b["tenants"],
                        "windows": windows})
        return out

    prime = replay()   # cold value caches: every pool request once
    steady = replay()  # every unsalted value cached
    again = replay()   # fresh salts: the steady shapes must hold
    tier_shapes: dict = {lane: {} for lane in LANES}
    for b, c in zip(steady, again):
        for name, w in b["windows"].items():
            if w != c["windows"][name]:
                raise SystemExit(f"steady plan moved on replay: {w} != {c['windows'][name]}")
            if w["requests"] != tspec["composition"][names.index(name)]:
                raise SystemExit(f"a burst holds {w['requests']} requests of text {name}")
            sets = tier_shapes[b["lane"]].setdefault(name, [])
            if w["tier_shapes"] not in sets:
                # A window is as wide as its longest value, cached or
                # not, so a lane and text may see a few shape sets; every
                # burst goes out in every warm round, so all are warm.
                sets.append(w["tier_shapes"])
                sets.sort()
    plan = {
        "tier_shapes": tier_shapes,
        "composition": dict(zip(names, tspec["composition"])),
        "prime": prime,
        "steady": steady,
        "left_out": {"too_wide": 0, "unplaced": 0},
        "requests_per_pass": sum(len(b["requests"]) for b in steady),
    }
    print(f"plan: {len(steady)} bursts, {plan['requests_per_pass']} requests a pass, steady "
          f"shapes {json.dumps(tier_shapes)}, prime shapes "
          f"{sorted({str(w['tier_shapes']) for b in prime for w in b['windows'].values()})}",
          file=sys.stderr)

    with open(cdir / "corpus.jsonl", "w") as fh:
        for i, wire in enumerate(pool):
            status, rule_id = reference[i]
            fh.write(json.dumps({
                "id": raw[i][0], "wire": base64.b64encode(wire).decode(), "status": status,
                "rule_id": rule_id, "declared": [],
                "tenant": instances[rank_of[i]]["instance"], "text": names[text_of[i]],
            }) + "\n")
    (cdir / "plans").mkdir(exist_ok=True)
    for pname in spec["plans"]:
        (cdir / "plans" / f"{pname}.json").write_text(json.dumps(plan) + "\n")
    by_tenant = [sum(1 for i in rank_of if rank_of[i] == r) for r in range(len(instances))]
    per_pass = [sum(1 for b in steady for i in b["requests"] if rank_of[i] == r)
                for r in range(len(instances))]
    summary = {
        "pool_requests": len(pool),
        "pool_copy_of": spec["pool_copy_of"],
        "blocked": sum(reference[i][0] != 200 for i in range(len(pool))),
        "allowed": sum(reference[i][0] == 200 for i in range(len(pool))),
        "left_out": {"salt_moves_verdict": 0, "declared_status_differs": 0},
        "salt_seeds": seeds,
        "tenants": len(instances),
        "rule_texts": n_texts,
        "rules_compiled": {names[t]: len(e.rule_meta) for t, e in enumerate(engines)},
        "rules_skipped": {names[t]: len(e.compiled.report.skipped)
                          for t, e in enumerate(engines)},
        "zipf_s": tspec["zipf_s"],
        "expected_requests_a_burst_by_text": [round(x, 3) for x in expected],
        "composition": plan["composition"],
        "pool_requests_by_text": {names[t]: sum(1 for i in text_of if text_of[i] == t)
                                  for t in range(n_texts)},
        "pool_requests_by_tenant_rank": by_tenant,
        "requests_a_pass_by_tenant_rank": per_pass,
        "rank_1_share_of_a_pass": round(per_pass[0] / plan["requests_per_pass"], 4),
        "tenants_named_a_burst": [min(b["tenants"] for b in steady),
                                  max(b["tenants"] for b in steady)],
        "verdict_depends_on_tenant": len(depends),
        "verdict_depends_on_tenant_by_text": dict(zip(names, depends_by_text)),
    }
    (cdir / "frozen.json").write_text(json.dumps(summary, indent=1) + "\n")
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
