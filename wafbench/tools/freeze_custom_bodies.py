#!/usr/bin/env python3
"""Build-time tool: a site's ``@rx`` feed in front of an API, frozen into
data files: ``crs-lite-pl2-custom5k``'s rule text under
``crs-lite-pl2-bodies``' bodied traffic, with custom requests that carry a
feed rule's tokens inside a JSON, form or multipart body.

    JAX_PLATFORMS=cpu CKO_NATIVE_LIB=build/wafbench/libcko_native.so \
        python3 -m wafbench.tools.freeze_custom_bodies \
        wafbench/configs/crs-lite-pl2-custom5k-bodies

Imports what ``freeze_bodies.py`` (the bodied pool's generator) and
``freeze_custom.py`` (the feed and its near-misses) already have and edits
neither. Reads ``freeze.json`` in the configuration's directory and writes

1. ``rules/``: ``rules_base``'s tree and the feed of ``feed_seed``, which
   is ``crs-lite-pl2-custom5k/rules`` byte for byte;
2. ``corpus.jsonl``: ``pool_base``'s bodied requests, each verdict computed
   again on the whole text by the plain host evaluator on 4 salts, then per
   picked feed rule one bodied request that rule blocks and its near-miss
   (one byte of the rule's last token changed) that the whole text allows.
   Templates b and d carry their tokens in a body field, a in the path and
   c in the ``User-Agent`` of a bodied request; the bodies are the pool
   generator's own, at sizes drawn from the pool's distribution;
3. ``plans/<plan>.json``: ``bursts`` of ``steady_from``'s steady groups,
   sent twice a cycle, each time with one custom request, blocked and
   near-miss alternating (as ``crs-custom5k``'s plan does), so a cycle is
   ``2 * bursts`` bursts and sends every custom request once; and a prime
   pass over exactly the requests the steady bursts send; every group
   checked with the engine's own tensorizer and a replica of its value
   cache to be one window on the plan's one matcher shape;
4. ``frozen.json``: what came out.

Like its sisters it is never run by a benchmark run.
"""

from __future__ import annotations

import argparse
import base64
import itertools
import json
import math
import os
import random
import sys
from pathlib import Path

from wafbench.generators.planned_bursts import SALT_TOKEN, salt_for
from wafbench.harness import read_rules
from wafbench.tools.freeze_bodies import bodies_pool, materialize
from wafbench.tools.freeze_custom import (
    TEMPLATE_OF,
    feed_rules,
    near_miss,
    write_rules,
)

# how many pairs a template gets, and which body kinds carry the tokens of
# the fifteen b and d pairs, in the order of their feed indexes
PAIRS = {"a": 5, "b": 10, "c": 4, "d": 5}
FIELD_KINDS = ("json", "urlencoded", "json", "multipart", "json", "urlencoded", "json", "json",
               "urlencoded", "json", "multipart", "urlencoded", "json", "urlencoded", "json")
CTYPES = {"json": "application/json", "urlencoded": "application/x-www-form-urlencoded",
          "multipart": "multipart/form-data"}


def picks(n: int) -> list[int]:
    """Feed indexes whose rules get a pair: ``PAIRS`` of each template,
    spread evenly, the feed's first and last rule among them."""
    chosen = {0, n - 1}
    for tpl, k in PAIRS.items():
        mine = [i for i in range(n) if TEMPLATE_OF[i % 10] == tpl]
        have = sum(TEMPLATE_OF[i % 10] == tpl for i in chosen)
        step = itertools.count(1)
        while have < k:
            i = mine[(next(step) * len(mine)) // (k + 1) % len(mine)]
            if i not in chosen:
                chosen.add(i)
                have += 1
    return sorted(chosen)


def pair_sizes(spec: dict) -> list[int]:
    """A body size a pair from the pool's own distribution; every third
    pair is drawn again until it is over ``long_over``."""
    rng = random.Random(spec["custom_seed"])
    dist = spec["body_bytes"]
    lo, hi = dist["clip"]

    def draw() -> int:
        return int(min(hi, max(lo, math.exp(rng.gauss(math.log(dist["median"]), dist["sigma"])))))

    sizes = []
    for k in range(sum(PAIRS.values())):
        want = draw()
        while k % 3 == 0 and want <= spec["long_over"]:
            want = draw()
        sizes.append(want)
    return sizes


def bodied(spec: dict, tag: str, kind: str, want: int, value: str | None) -> bytes:
    """One request of the bodied pool's generator: ``kind`` body of
    ``want`` bytes at send time, one field holding ``value``."""
    one = {"pool_seed": f"{spec['custom_seed']}/{tag}", "salt_hex": spec["salt_hex"],
           "pool_requests": 1, "pool_spare": 0, "attack_share": 1.0 if value else 0.0,
           "content_types": {"json": float(kind == "json"),
                             "urlencoded": float(kind == "urlencoded")},
           "body_bytes": {"median": want, "sigma": 0.0, "clip": spec["body_bytes"]["clip"]}}
    return bodies_pool(one, [value])[0]["wire"]


def custom_requests(rules: list[dict], spec: dict) -> list[dict]:
    """Per picked rule a bodied request it blocks and the near-miss: the
    same request with the last byte of the rule's last token changed."""
    rng = random.Random(f"{spec['custom_seed']}/kinds")
    share = spec["content_types"]
    sizes = pair_sizes(spec)
    field_kinds = iter(FIELD_KINDS)
    out = []
    for k, i in enumerate(picks(len(rules))):
        r = rules[i]
        t, tpl = r["tokens"], r["template"]
        if tpl in "bd":
            kind = next(field_kinds)
        else:
            kind = rng.choices(list(share), weights=list(share.values()))[0]
        for near in (False, True):
            u = [near_miss(x) if near and j == len(t) - 1 else x for j, x in enumerate(t)]
            value = None
            if tpl == "b":
                value = f"{u[0]}({u[1]}"
            elif tpl == "d":
                value = f"{u[k % 3]}_{k}k=v{u[3]}"
            wire = bodied(spec, f"custom/{r['id']}", kind, sizes[k], value)
            line, rest = wire.split(b"\r\n", 1)
            if tpl == "a":
                method = line.split(b" ", 1)[0]
                line = method + f" /{u[0]}/{u[1]}/{u[2]}.php HTTP/1.1".encode()
            elif tpl == "c":
                before, _, after = rest.partition(b"User-Agent: ")
                rest = before + f"User-Agent: {u[0]}/2.{k}".encode() + b"\r\n" + \
                    after.split(b"\r\n", 1)[1]
            out.append({"id": f"custom-{r['id']}-{'near' if near else 'hit'}",
                        "wire": line + b"\r\n" + rest, "rule": r["id"], "near": near,
                        "template": tpl, "kind": kind,
                        "carrier": {"a": "uri", "c": "user-agent"}.get(tpl, kind + " field")})
    return out


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
    salt_hex, rows_max = spec["salt_hex"], spec["rows_max"]
    rules = feed_rules(spec["feed_rules"], spec["feed_seed"])
    write_rules(cdir, (cdir.parent / spec["rules_base"]).resolve(), rules)
    config = json.loads((cdir / "config.json").read_text())
    text = read_rules(cdir / config["rules"])
    engine = WafEngine(text)
    if not engine._native.available:
        raise SystemExit("native tensorizer not loaded (set CKO_NATIVE_LIB): "
                         "the plan must be made by the tensorizer the sidecar uses")
    if engine.value_cache is None:
        raise SystemExit("value cache is a shipped default; engine has none")

    # -- the pool: the base's bodied requests, then the custom pairs ---------------
    pool_dir = (cdir.parent / spec["pool_base"]).resolve()
    base_pool = [json.loads(line) for line in open(pool_dir / "corpus.jsonl")]
    raw = [(r["id"], base64.b64decode(r["wire"]), (r["status"], r["rule_id"]))
           for r in base_pool]
    customs = custom_requests(rules, spec)
    raw += [(c["id"], c["wire"], (200, None) if c["near"] else (403, str(c["rule"])))
            for c in customs]

    seeds = list(range(1, spec["salt_seeds"] + 1)) + [2**31 + 12345]
    by_seed = []
    for seed in seeds:
        reqs = [materialize(w, salt_for(seed, "freeze", i, salt_hex))
                for i, (_id, w, _v) in enumerate(raw)]
        by_seed.append([(v.status if v.interrupted else 200,
                         str(v.rule_id or 0) if v.interrupted else None)
                        for v in engine.host_fallback.evaluate(reqs)])
        print(f"reference verdicts, salt seed {seed}: done", file=sys.stderr)
    pool, new_index, moved = [], {}, []
    for i, (rid, wire, want) in enumerate(raw):
        got = {vs[i] for vs in by_seed}
        if got != {want}:
            if i >= len(base_pool):
                raise SystemExit(f"{rid}: the whole text says {sorted(got, key=str)}, not {want}")
            moved.append(rid)
            continue
        new_index[i] = len(pool)
        pool.append({"id": rid, "wire": wire, "status": want[0], "rule_id": want[1]})
    n_base = len(base_pool) - len(moved)
    hits = [i for i in range(n_base, len(pool)) if pool[i]["rule_id"]]
    nears = [i for i in range(n_base, len(pool)) if not pool[i]["rule_id"]]

    # -- the plan ---------------------------------------------------------------------
    source = json.loads((pool_dir / "plans" / f"{spec['steady_from']}.json").read_text())
    want_shapes = source["tier_shapes"]
    (_rows_want, width_want), = want_shapes
    serial = itertools.count(10**9)

    def body_bytes(i: int) -> int:
        return len(pool[i]["wire"].partition(b"\r\n\r\n")[2]) - len(SALT_TOKEN) + salt_hex

    def place(idxs, keep: bool):
        """(the window's executables, its tier shapes, unique uncached rows
        a tier, the post stage's row pairs and cached rows) of one window of
        pool requests ``idxs`` on fresh salts; ``keep`` caches its rows as a
        served window does."""
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
        post = [[int(t[5].shape[0]), 0 if c is None else int(c.shape[0])]
                for t, c in zip(tiers, cached)]
        return (tuple(spec_key(s) for s in match_specs + [post_spec]),
                [list(t[0].shape) for t in tiers], [len(k) for k in miss_keys], post, miss_keys)

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

    def one_window(placed) -> bool:
        shapes, n_miss = placed[1:3]
        return len(shapes) == 1 and shapes[0][1] == width_want and n_miss[0] <= rows_max

    # Steady: ``bursts`` of the base plan's groups, twice over, each burst
    # with one custom request: a rule's blocked request, then its near-miss.
    n_bursts = spec["bursts"]
    if len(hits) != n_bursts or len(nears) != n_bursts:
        raise SystemExit(f"{len(hits)} blocked and {len(nears)} near-miss requests "
                         f"for {n_bursts} bursts")
    groups = [[new_index[i] for i in g["requests"]] for g in source["steady"]
              if all(i in new_index for i in g["requests"])]
    # Of the base plan's groups, those whose requests bring the fewest rows
    # that no other group's requests bring: what the prime pass has to seed.
    cold()
    keys_of = [{k for i in g for keys in place([i], keep=False)[4] for k in keys}
               for g in groups]
    owners: dict = {}
    for keys in keys_of:
        for k in keys:
            owners[k] = owners.get(k, 0) + 1
    own_rows = [sum(owners[k] == 1 for k in keys) for keys in keys_of]
    cheapest = sorted(sorted(range(len(groups)), key=lambda g: (own_rows[g], g))[:n_bursts])
    steady_groups = [groups[cheapest[k % n_bursts]] + [(nears if k % 2 else hits)[k // 2]]
                     for k in range(2 * n_bursts)]
    sent = sorted({i for g in steady_groups for i in g})

    # Prime: every request the steady bursts send, once, from a cold cache.
    # Each group is led by a long request, which brings the window to the
    # long width: one not sent yet while there is one (it costs nothing that
    # the pass does not owe anyway), then one already seen (its salted rows);
    # it takes of what is left, smallest first, whatever still fits the bucket.
    cold()
    is_long = {i: body_bytes(i) > spec["long_over"] for i in sent}
    # the base's requests by size, then the custom pairs, a near-miss next to
    # its blocked twin (it is new by one value only)
    todo = sorted(sent, key=lambda i: (i >= n_base, body_bytes(i), pool[i]["id"]))
    prime, seen = [], []
    while todo:
        seen_long = [i for i in seen if is_long[i]]
        lead = next((i for i in todo if is_long[i] and one_window(place([i], False))), None)
        if lead is not None:
            todo.remove(lead)
            group = [lead]
        elif seen_long:
            group = [seen_long[len(prime) % len(seen_long)]]
        else:
            raise SystemExit(f"no long request lands on {want_shapes} cold")
        for i in list(todo):
            placed = place(group + [i], keep=False)
            if one_window(placed):
                group.append(i)
                todo.remove(i)
                if placed[2][0] > rows_max - 3:  # the salted rows of one more do not fit
                    break
        fill = itertools.cycle(seen or group)
        while place(group, keep=False)[1] != want_shapes:
            group.append(next(fill))
        if not set(group) - set(seen):
            raise SystemExit(f"request {todo[0]} fits no prime group on {want_shapes}")
        _sig, shapes, n_miss, post, _keys = place(group, keep=True)
        if shapes != want_shapes or n_miss[0] > rows_max:
            raise SystemExit(f"prime group {group} lands on {shapes} with {n_miss} rows")
        seen += [i for i in group if i not in seen]
        prime.append({"lane": "bulk", "requests": group, "unique_uncached_rows": n_miss[0],
                      "tier_shapes": shapes, "post_shapes": post})
    # The pass ends with the first steady burst, every value of it seen: its
    # window mints the steady bursts' post stage, so that the first warm round
    # mints nothing and is the only one.
    _sig, shapes, n_miss, post, _keys = place(steady_groups[0], keep=True)
    prime.append({"lane": "bulk", "requests": steady_groups[0],
                  "unique_uncached_rows": n_miss[0], "tier_shapes": shapes, "post_shapes": post})
    if shapes != want_shapes or len(prime) > spec["prime_groups_max"]:
        raise SystemExit(f"{len(prime)} prime groups (at most {spec['prime_groups_max']}), "
                         f"the last on {shapes}")

    steady, sigs = [], []
    for g in steady_groups:
        sig, shapes, n_miss, post, _keys = place(g, keep=True)
        sig2, again, n_miss2 = place(g, keep=True)[:3]
        if shapes != want_shapes or again != want_shapes or n_miss != n_miss2 \
                or n_miss[0] > rows_max or sig != sig2:
            raise SystemExit(f"steady group {g} lands on {shapes} with {n_miss} / {n_miss2} rows")
        sigs.append(sig)
        steady.append({"lane": "bulk", "requests": g, "unique_uncached_rows": n_miss[0],
                       "tier_shapes": shapes, "post_shapes": post,
                       "long_bodies": sum(is_long[i] for i in g),
                       "wire_bytes": sum(len(pool[i]["wire"]) for i in g)})
    plan = {"tier_shapes": want_shapes, "steady_from": f"{spec['pool_base']}/{spec['steady_from']}",
            "prime": prime, "steady": steady,
            "requests_per_pass": sum(len(g) for g in steady_groups),
            "left_out": {"pool_requests_never_sent": len(pool) - len(sent)}}

    with open(cdir / "corpus.jsonl", "w") as fh:
        for r in pool:
            fh.write(json.dumps({
                "id": r["id"], "wire": base64.b64encode(r["wire"]).decode(),
                "status": r["status"], "rule_id": r["rule_id"], "declared": [],
            }) + "\n")
    (cdir / "plans").mkdir(exist_ok=True)
    (cdir / "plans" / f"{spec['plan']}.json").write_text(json.dumps(plan) + "\n")

    def ctype(i: int) -> str:
        head = pool[i]["wire"].partition(b"\r\n\r\n")[0].decode("latin-1")
        return next(k for k, v in CTYPES.items() if f"Content-Type: {v}" in head)

    auto = engine.automata_summary()
    summary = {
        "pool_requests": len(pool),
        "base_requests": n_base,
        "custom_requests": len(pool) - n_base,
        "blocked": sum(r["status"] != 200 for r in pool),
        "allowed": sum(r["status"] == 200 for r in pool),
        "moved_by_feed": moved,
        "by_content_type": {k: sum(ctype(i) == k for i in range(n_base)) for k in CTYPES},
        "custom_blocked_by": sorted(int(pool[i]["rule_id"]) for i in hits),
        "custom_pairs": {
            "by_template": {t: sum(c["template"] == t and not c["near"] for c in customs)
                            for t in PAIRS},
            "by_carrier": {w: sum(c["carrier"] == w and not c["near"] for c in customs)
                           for w in sorted({c["carrier"] for c in customs})},
            "by_content_type": {k: sum(c["kind"] == k and not c["near"] for c in customs)
                                for k in CTYPES},
            "body_bytes_over_long": sum(body_bytes(i) > spec["long_over"] for i in hits),
            "body_bytes": sorted(body_bytes(i) for i in hits),
        },
        "salt_seeds": seeds,
        "rules_compiled": len(engine.rule_meta),
        "rules_skipped": len(engine.compiled.report.skipped),
        "feed_rules": len(rules),
        "automata_summary": {k: auto[k] for k in (
            "rules", "segment_columns", "segment_splits", "segment_split_groups",
            "segment_long_groups", "flat_bins", "flat_slots", "flat_groups", "per_bank_kernels")},
        "plan": {"prime_groups": len(prime), "steady_groups": len(steady),
                 "requests_per_pass": plan["requests_per_pass"],
                 "pool_requests_sent": len(sent) - len(hits) - len(nears),
                 "steady_rows": sorted({b["unique_uncached_rows"] for b in steady}),
                 "prime_rows": sorted({b["unique_uncached_rows"] for b in prime}),
                 "steady_executable_sets": len(set(sigs)),
                 "steady_post_shapes": sorted({str(b["post_shapes"]) for b in steady}),
                 "steady_long_bodies": sorted({b["long_bodies"] for b in steady}),
                 "steady_wire_bytes_max": max(b["wire_bytes"] for b in steady)},
    }
    (cdir / "frozen.json").write_text(json.dumps(summary, indent=1) + "\n")
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
