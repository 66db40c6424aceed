#!/usr/bin/env python3
"""Build-time tool: a CRS configuration with a site's own ``@rx`` feed in
the slot CRS documents for site rules, frozen into data files.

    JAX_PLATFORMS=cpu CKO_NATIVE_LIB=build/wafbench/libcko_native.so \
        python3 -m wafbench.tools.freeze_custom wafbench/configs/crs-lite-pl2-custom5k

Reads ``freeze.json`` in the configuration's directory (the base
configuration, the feed's seed and size, the base plan the bursts come
from) and writes

1. ``rules/``: the base configuration's rule tree byte for byte, and
   ``REQUEST-900-CUSTOM-FEED.conf`` from ``feed_rules`` (seeded; template
   by ``i mod 10``; the tokens lower-case letters, all distinct);
2. ``corpus.jsonl``: the base's pool, each request's reference verdict
   computed again on the whole text by the plain host evaluator on 4
   salts (one whose verdict the feed moves is counted ``moved_by_feed``
   and left out), then the custom requests: per picked feed rule one
   request that rule blocks and its near-miss (one byte of a token
   changed) that the whole text allows;
3. ``plans/<plan>.json``: the base plan's steady groups, each with one
   custom request of its lane (blocked and near-miss alternating), and
   its prime groups with the custom requests placed among them; every
   group checked with the engine's own tensorizer and a replica of its
   value cache to be one window on the plan's ``tier_shapes``;
4. ``frozen.json``: what came out, the automata plan's counts among it.

Like ``freeze_config.py`` it is never run by a benchmark run.
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

FEED_FILE = "REQUEST-900-CUSTOM-FEED.conf"
FEED_BASE_ID = 9000000
_LETTERS = "abcdefghijklmnopqrstuvwxyz"
# template by i mod 10: 40% a, 30% b, 20% c, 10% d
TEMPLATE_OF = "aaaabbbccd"
_UA = "Mozilla/5.0 (X11; Linux x86_64) Firefox/115.0"


def _tokens(rng: random.Random, seen: set, *lengths: int) -> list[str]:
    out = []
    for n in lengths:
        while True:
            tok = "".join(rng.choice(_LETTERS) for _ in range(n))
            if tok not in seen:
                break
        seen.add(tok)
        out.append(tok)
    return out


def feed_rules(n: int, seed: int) -> list[dict]:
    """The feed: ``n`` rules ``{id, template, variable, pattern, transforms,
    tokens}``. a: path patches; b: parameter signatures; c: agent
    signatures; d: keyed values."""
    rng = random.Random(seed)
    seen: set = set()
    rules = []
    for i in range(n):
        template = TEMPLATE_OF[i % 10]
        if template == "a":
            t = _tokens(rng, seen, 6, 8, 5)
            var, tr = "REQUEST_URI", "t:none,t:urlDecodeUni"
            pat = rf"(?i:/{t[0]}/{t[1]}/{t[2]}\.php)"
        elif template == "b":
            t = _tokens(rng, seen, 6, 5)
            var, tr = "ARGS", "t:none,t:urlDecodeUni"
            pat = rf"(?i:{t[0]}\s*\(\s*['\"]?{t[1]})"
        elif template == "c":
            t = _tokens(rng, seen, 7)
            var, tr = "REQUEST_HEADERS:User-Agent", "t:none,t:lowercase"
            pat = rf"{t[0]}/[0-9]+\.[0-9]+"
        else:
            t = _tokens(rng, seen, 4, 5, 6, 4)
            var, tr = "ARGS|REQUEST_COOKIES", "t:none,t:urlDecodeUni"
            pat = rf"(?i:(?:{t[0]}|{t[1]}|{t[2]})[a-z0-9_]{{2,8}}=[^&]*{t[3]})"
        rules.append({"id": FEED_BASE_ID + i, "template": template, "variable": var,
                      "pattern": pat, "transforms": tr, "tokens": t})
    return rules


def feed_text(rules: list[dict]) -> str:
    head = ("# A site's own @rx feed (virtual patches, parameter and agent signatures), in the\n"
            "# slot CRS documents for site rules. Written by wafbench/tools/freeze_custom.py.\n")
    return head + "".join(
        f'SecRule {r["variable"]} "@rx {r["pattern"]}" '
        f'"id:{r["id"]},phase:2,deny,status:403,log,{r["transforms"]},'
        f"msg:'custom {r['id'] - FEED_BASE_ID}'\"\n" for r in rules)


def picks(n: int, pairs: int = 23) -> list[int]:
    """Feed indexes whose rules get a request pair: the feed's first and
    last rule, the first and last of template d, and the rest spread
    evenly, at least 4 of every template."""
    of = lambda tpl: [i for i in range(n) if TEMPLATE_OF[i % 10] == tpl]
    d = of("d")
    want = {"a": 7, "b": 6, "c": 5, "d": 5}
    assert sum(want.values()) == pairs
    chosen = {0, n - 1, d[0], d[-1]}
    for tpl, k in want.items():
        mine = of(tpl)
        have = [i for i in chosen if TEMPLATE_OF[i % 10] == tpl]
        step = itertools.count(1)
        while len(have) < k:
            i = mine[(next(step) * len(mine)) // (k + 1) % len(mine)]
            if i not in chosen:
                chosen.add(i)
                have.append(i)
    return sorted(chosen)


def near_miss(tok: str) -> str:
    """``tok`` with its last byte changed."""
    return tok[:-1] + ("x" if tok[-1] != "x" else "y")


def custom_requests(rules: list[dict], salt_arg: str) -> list[dict]:
    """Per picked rule a request it blocks and the near-miss, as wire
    templates. Every third pair is a form POST (the bulk lane); a
    template c rule reads a header, so its pair is always a GET."""
    salt = SALT_TOKEN.decode()
    out = []
    posts = 0
    for k, i in enumerate(picks(len(rules))):
        r = rules[i]
        t = r["tokens"]
        post = r["template"] != "c" and posts < 3 and k % 3 == 1
        posts += post
        for near in (False, True):
            u = [near_miss(x) if near and j == len(t) - 1 else x for j, x in enumerate(t)]
            ua, path, arg = _UA, "/app/view", None
            if r["template"] == "a":
                path = f"/{u[0]}/{u[1]}/{u[2]}.php"
            elif r["template"] == "b":
                arg = f"q={u[0]}({u[1]}"
            elif r["template"] == "c":
                ua = f"{u[0]}/2.{k}"
            else:
                arg = f"ref={u[k % 3]}_{k}k%3Dv{u[3]}"
            if post:
                body = (arg or "note=ok") + f"&{salt_arg}={salt}"
                n = len(body) - len(SALT_TOKEN)
                head = (f"POST {path} HTTP/1.1\r\nHost: localhost\r\nUser-Agent: {ua}\r\n"
                        "Content-Type: application/x-www-form-urlencoded\r\n"
                        "Content-Length: {LEN}\r\n\r\n")
                wire = (head + body, n)
            else:
                query = (arg + "&" if arg else "") + f"{salt_arg}={salt}"
                wire = (f"GET {path}?{query} HTTP/1.1\r\nHost: localhost\r\n"
                        f"User-Agent: {ua}\r\n\r\n", None)
            out.append({"id": f"custom-{r['id']}-{'near' if near else 'hit'}", "wire": wire,
                        "rule": r["id"], "near": near, "post": post})
    return out


def wire_bytes(wire: tuple, salt_hex: int) -> bytes:
    """A custom request's template: a POST's Content-Length counts the
    salt it will carry."""
    text, unsalted = wire
    if unsalted is not None:
        text = text.replace("{LEN}", str(unsalted + salt_hex))
    return text.encode()


def write_rules(cdir: Path, base_dir: Path, rules: list[dict]) -> None:
    """``rules/``: the base's tree as it is, and the feed beside it."""
    shutil.rmtree(cdir / "rules", ignore_errors=True)
    shutil.copytree(base_dir / "rules", cdir / "rules")
    (cdir / "rules" / FEED_FILE).write_text(feed_text(rules))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_dir", type=Path)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this tool must not take a chip")

    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.value_cache import ValueHitCache
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine, warmup_request
    from wafbench.tools.freeze_bodies import materialize

    cdir = args.config_dir.resolve()
    spec = json.loads((cdir / "freeze.json").read_text())
    base_dir = (cdir.parent / spec["base"]).resolve()
    salt_hex = spec["salt_hex"]
    rules = feed_rules(spec["feed_rules"], spec["feed_seed"])
    write_rules(cdir, base_dir, rules)
    config = json.loads((cdir / "config.json").read_text())
    text = read_rules(cdir / config["rules"])
    engine = WafEngine(text)
    if not engine._native.available:
        raise SystemExit("native tensorizer not loaded (set CKO_NATIVE_LIB): "
                         "the plan must be made by the tensorizer the sidecar uses")
    if engine.value_cache is None:
        raise SystemExit("value cache is a shipped default; engine has none")

    # -- the pool: the base's requests, then the custom pairs ---------------------
    base_pool = [json.loads(line) for line in open(base_dir / "corpus.jsonl")]
    raw = [(r["id"], base64.b64decode(r["wire"]), r["declared"], (r["status"], r["rule_id"]))
           for r in base_pool]
    customs = custom_requests(rules, spec["salt_arg"])
    raw += [(c["id"], wire_bytes(c["wire"], salt_hex), [],
             (200, None) if c["near"] else (403, str(c["rule"]))) for c in customs]

    seeds = list(range(1, spec["salt_seeds"] + 1)) + [2**31 + 12345]
    verdicts_by_seed = []
    for seed in seeds:
        reqs = [materialize(w, salt_for(seed, "freeze", i, salt_hex))
                for i, (_id, w, _d, _v) in enumerate(raw)]
        verdicts_by_seed.append(
            [(v.status if v.interrupted else 200,
              str(v.rule_id or 0) if v.interrupted else None)
             for v in engine.host_fallback.evaluate(reqs)])
        print(f"reference verdicts, salt seed {seed}: done", file=sys.stderr)
    pool, new_index, moved = [], {}, []
    for i, (rid, wire, declared, want) in enumerate(raw):
        got = {vs[i] for vs in verdicts_by_seed}
        if got != {want}:
            if i >= len(base_pool):
                raise SystemExit(f"{rid}: the whole text says {sorted(got, key=str)}, not {want}")
            moved.append(rid)
            continue
        new_index[i] = len(pool)
        pool.append({"id": rid, "wire": wire, "status": want[0], "rule_id": want[1],
                     "declared": declared})
    n_base = len(base_pool) - len(moved)

    # -- the plan -------------------------------------------------------------------
    source = json.loads((base_dir / "plans" / f"{spec['steady_from']}.json").read_text())
    want_shapes = source["tier_shapes"]
    hi, least = spec["miss_hi"], spec["miss_min"]
    serial = itertools.count(10**9)

    def lane_of(i: int) -> str:
        head, _, body = pool[i]["wire"].partition(b"\r\n\r\n")
        return "bulk" if body and not head.startswith(b"GET ") else "interactive"

    def place(idxs, keep: bool):
        """(tier shapes, unique uncached rows of the first tier) of one
        window of pool requests ``idxs`` on fresh salts; ``keep`` caches
        its rows as a served window does."""
        reqs = [materialize(pool[i]["wire"], salt_for(0, "freeze", next(serial), salt_hex))
                for i in idxs]
        tiers, _nv, _masks, _cached, miss_keys, lease = engine._batch_tensors(reqs)
        if lease is not None:
            lease.release()
        if keep:
            for keys in miss_keys:
                if keys:
                    engine.value_cache.insert(
                        keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8))
        return [list(t[0].shape) for t in tiers], len(miss_keys[0])

    def cold():
        engine.value_cache = ValueHitCache(engine.value_cache.packed_len,
                                           engine.value_cache.max_bytes)
        place_warm = engine._batch_tensors([warmup_request()])
        if place_warm[5] is not None:
            place_warm[5].release()
        for keys in place_warm[4]:
            if keys:
                engine.value_cache.insert(
                    keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8))

    def mapped(group: dict) -> list[int]:
        return [new_index[i] for i in group["requests"] if i in new_index]

    custom_idx = {lane: [i for i in range(n_base, len(pool)) if lane_of(i) == lane]
                  for lane in ("interactive", "bulk")}
    steady_lanes = [g["lane"] for g in source["steady"]]
    for lane, mine in custom_idx.items():
        if len(mine) != steady_lanes.count(lane):
            raise SystemExit(f"{len(mine)} custom requests for {steady_lanes.count(lane)} "
                             f"{lane} bursts")

    # Prime: the base's groups in their order, each taking the next custom
    # requests of its lane while it stays one window of the wanted width
    # and rows; what is left goes out in groups of its own, the last of
    # them topped up with custom requests already sent (their salted
    # rows are new again) until it holds the rows of the wanted bucket.
    widths = [w[1] for w in want_shapes]

    def fits(idxs) -> bool:
        shapes, n = place(idxs, keep=False)
        return [s[1] for s in shapes] == widths and n <= hi

    cold()
    prime, waiting = [], {lane: list(mine) for lane, mine in custom_idx.items()}
    for g in source["prime"]:
        idxs = mapped(g)
        while waiting[g["lane"]] and fits(idxs + waiting[g["lane"]][:1]):
            idxs.append(waiting[g["lane"]].pop(0))
        prime.append((g["lane"], idxs))
        place(idxs, keep=True)
    for lane, rest in waiting.items():
        group: list[int] = []
        for i in rest:
            if group and not fits(group + [i]):
                prime.append((lane, group))
                place(group, keep=True)
                group = []
            group.append(i)
        sent = itertools.cycle([i for i in custom_idx[lane] if i not in group])
        while group and place(group, keep=False)[1] < least:
            group.append(next(sent))
        if group:
            prime.append((lane, group))
            place(group, keep=True)
    cold()
    plan = {"tier_shapes": want_shapes, "steady_from": f"{spec['base']}/{spec['steady_from']}",
            "prime": [], "steady": [], "left_out": dict(source["left_out"])}
    for lane, idxs in prime:
        shapes, n = place(idxs, keep=True)
        if shapes != want_shapes or not least <= n <= hi:
            raise SystemExit(f"prime group {idxs} lands on {shapes} with {n} rows")
        plan["prime"].append({"lane": lane, "requests": idxs, "unique_uncached_rows": n,
                              "tier_shapes": shapes})

    # Steady: group k of the base plan and the k-th custom request of its
    # lane, on fresh salts with every unsalted value seen before.
    taken = {lane: iter(mine) for lane, mine in custom_idx.items()}
    for g in source["steady"]:
        idxs = mapped(g) + [next(taken[g["lane"]])]
        shapes, n = place(idxs, keep=True)
        again, n2 = place(idxs, keep=True)
        if shapes != want_shapes or again != want_shapes or n != n2 or not least <= n <= hi:
            raise SystemExit(f"steady group {idxs} lands on {shapes} with {n} / {n2} rows")
        plan["steady"].append({"lane": g["lane"], "requests": idxs, "unique_uncached_rows": n,
                               "tier_shapes": shapes})
    plan["requests_per_pass"] = sum(len(b["requests"]) for b in plan["steady"])

    with open(cdir / "corpus.jsonl", "w") as fh:
        for r in pool:
            fh.write(json.dumps({
                "id": r["id"], "wire": base64.b64encode(r["wire"]).decode(),
                "status": r["status"], "rule_id": r["rule_id"], "declared": r["declared"],
            }) + "\n")
    (cdir / "plans").mkdir(exist_ok=True)
    (cdir / "plans" / f"{spec['plan']}.json").write_text(json.dumps(plan) + "\n")

    tiers = engine.automata_plan.tiers
    kinds = sorted({t.kind for t in tiers})
    auto = engine.automata_summary()
    by_template = {tpl: sorted({engine.automata_plan.kind_of(g) for g in gids})
                   for tpl, gids in _feed_groups(engine, rules).items()}
    summary = {
        "pool_requests": len(pool),
        "base_requests": n_base,
        "custom_requests": len(pool) - n_base,
        "blocked": sum(r["status"] != 200 for r in pool),
        "allowed": sum(r["status"] == 200 for r in pool),
        "moved_by_feed": moved,
        "custom_blocked_by": sorted(int(r["rule_id"]) for r in pool[n_base:] if r["rule_id"]),
        "salt_seeds": seeds,
        "rules_compiled": len(engine.rule_meta),
        "rules_skipped": len(engine.compiled.report.skipped),
        "feed_rules": len(rules),
        "groups": len(tiers),
        "plan_automata": {"groups": {k: sum(t.kind == k for t in tiers) for k in kinds},
                          "states": {k: sum(t.n_states for t in tiers if t.kind == k)
                                     for k in kinds}},
        "feed_tier_by_template": by_template,
        "automata_summary": {k: auto[k] for k in
                             ("flat_bins", "flat_slots", "flat_groups", "per_bank_kernels")},
        "plan": {"prime_groups": len(plan["prime"]), "steady_groups": len(plan["steady"]),
                 "requests_per_pass": plan["requests_per_pass"],
                 "steady_rows": sorted({b["unique_uncached_rows"] for b in plan["steady"]}),
                 "prime_rows": sorted({b["unique_uncached_rows"] for b in plan["prime"]})},
    }
    (cdir / "frozen.json").write_text(json.dumps(summary, indent=1) + "\n")
    print(json.dumps(summary))
    return 0


def _feed_groups(engine, rules: list[dict]) -> dict[str, list[int]]:
    """Template -> the compiled groups of its feed rules."""
    template = {r["id"]: r["template"] for r in rules}
    out: dict[str, list[int]] = {}
    crs = engine.compiled
    for rule in crs.rules:
        if rule.rule_id in template:
            out.setdefault(template[rule.rule_id], []).extend(
                crs.links[k].group for k in rule.link_ids if crs.links[k].group >= 0)
    return out


if __name__ == "__main__":
    sys.exit(main())
