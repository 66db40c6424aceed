#!/usr/bin/env python3
"""Build-time tool: the bodied API request pool of a configuration, its
reference verdicts and its burst plans, frozen into data files.

    JAX_PLATFORMS=cpu CKO_NATIVE_LIB=build/wafbench/libcko_native.so \
        python3 -m wafbench.tools.freeze_bodies wafbench/configs/<name>

Sister of ``freeze_config.py`` (which is not edited and knows no bodied
pool): never run by a benchmark run, run once on the CPU by the PR that
adds the configuration, and its outputs (``corpus.jsonl``,
``plans/*.json``, ``frozen.json``) are committed as data. It reads
``freeze.json`` in the configuration's directory and

1. makes the pool from ``pool_seed``: every request a ``POST``/``PUT``/
   ``PATCH`` to an API-shaped path with a JSON, urlencoded or multipart
   body whose length at send time is log-normal, clipped; a share of
   them carry one attack payload, taken from the values the go-ftw
   corpus named in ``payload_corpus`` places in arguments and bodies.
   Every body has a ``nonce`` leaf / field / part holding ``SALT_TOKEN``,
   and so has the request id header an API client sends anyway;
2. computes each request's reference verdict with the plain host
   evaluator (``engine/host_fallback.py`` over ``engine/request.py``'s
   Python parsers) on several salts and keeps only requests whose
   verdict is the same on all of them;
3. plans bursts with the engine's own tensorizer and a replica of its
   value cache, so that every burst, cold (``prime``) and ``steady``,
   is one window on the plan's one set of executables.
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
from urllib.parse import parse_qsl, quote_plus, urlsplit

from wafbench.generators.planned_bursts import SALT_TOKEN, salt_for
from wafbench.harness import read_rules

SALT = SALT_TOKEN.decode()

_WORDS = (
    "order invoice customer shipping address street avenue apartment city region "
    "country postal phone mobile status pending shipped delivered cancelled refund "
    "payment card transfer currency amount total subtotal discount coupon quantity "
    "item product widget gadget sensor cable adapter battery blue green large small "
    "note comment please leave the parcel with a neighbour if nobody answers thanks "
    "meeting moved to monday morning agenda attached review budget quarter report "
    "café naïve Zürich München São façade résumé"
).split()
_KEYS = (
    "id name title email user account status type kind note comment description "
    "address city country phone amount total currency quantity sku ref source tags "
    "items meta options labels created updated owner channel locale reason"
).split()
_PATHS = (
    "/api/v1/orders", "/api/v1/orders/{n}", "/api/v1/users/{n}/profile",
    "/api/v1/carts/{n}/items", "/api/v2/invoices", "/api/v2/invoices/{n}/lines",
    "/api/v1/tickets", "/api/v1/tickets/{n}/comments", "/api/v2/uploads",
    "/api/v1/sessions", "/api/v2/accounts/{n}/settings", "/api/v1/search/saved",
)
_HOSTS = ("api.bench.local", "gateway.bench.local", "app.bench.local")
_AGENTS = (
    "Mozilla/5.0 (X11; Linux x86_64) AppleWebKit/537.36 Chrome/124.0.6367",
    "okhttp/4.12.0", "python-requests/2.31.0", "axios/1.6.8", "Go-http-client/1.1",
)
_ATTACK_FAMILIES = ("930", "932", "933", "934", "941", "942", "944")


def attack_payloads(corpus: Path) -> list[str]:
    """The values the go-ftw corpus places in arguments and urlencoded
    bodies of its blocked stages, families 930-944."""
    from coraza_kubernetes_operator_tpu.ftw.loader import load_tests_report

    tests, unparsable = load_tests_report(corpus)
    if unparsable:
        raise SystemExit(f"unparsable corpus files: {unparsable}")
    out: list[str] = []
    for t in tests:
        if not t.title.startswith(_ATTACK_FAMILIES):
            continue
        for s in t.stages:
            if s.response_status is not None or s.status != [403]:
                continue
            pairs = parse_qsl(urlsplit(s.uri).query, keep_blank_values=True)
            ctype = dict((k.lower(), v) for k, v in s.headers).get("content-type", "")
            if s.data and "urlencoded" in ctype:
                pairs += parse_qsl(s.data.decode("latin-1"), keep_blank_values=True)
            for _k, v in pairs:
                if 4 <= len(v) <= 200 and v.isascii() and v.isprintable() and v not in out:
                    out.append(v)
    return out


# -- bodies -------------------------------------------------------------------


def _fixed_atoms() -> tuple[str, ...]:
    """The fixed vocabulary short fields are drawn from: words, numbers,
    e-mail addresses and UUIDs."""
    rng = random.Random("wafbench api vocabulary")
    numbers = [str(rng.randrange(1, 10 ** rng.randrange(1, 7))) for _ in range(48)]
    mails = [f"{rng.choice(_WORDS[:40])}.{rng.choice(_WORDS[:40])}@example.org"
             for _ in range(48)]
    uuids = ["%08x-%04x-4%03x-a%03x-%012x" % (
        rng.getrandbits(32), rng.getrandbits(16), rng.getrandbits(12),
        rng.getrandbits(12), rng.getrandbits(48)) for _ in range(48)]
    return tuple(_WORDS) * 2 + tuple(numbers + mails + uuids)


_ATOMS = _fixed_atoms()


# Rule 920370: the deployment blocks an argument longer than this, so its
# clients are assumed not to send one (``config.json`` ``assumed`` says so
# and counts what the cap keeps out; no ``Authorization`` header either,
# for the reason given there).
_FIELD_MAX = 400


def _text(rng: random.Random, n: int) -> str:
    """Free text of the vocabulary, cut to ``n`` characters and to
    ``_FIELD_MAX`` bytes."""
    out = rng.choice(_ATOMS)
    while len(out) < n:
        out += " " + rng.choice(_ATOMS)
    out = out[:n]
    while len(out.encode()) > _FIELD_MAX:
        out = out[:-1]
    return out.rstrip()


def _values(rng: random.Random, n_leaves: int, budget: int, attack: str | None) -> list[str]:
    """``n_leaves`` string values: free-text fields of 16 to ``_FIELD_MAX``
    characters, as many as it takes (at least one to three) to share what
    ``budget`` the others leave, the others one atom of the fixed
    vocabulary each; one value is the attack payload."""
    n_text = min(n_leaves, max(rng.randrange(1, 4), -(-budget // (_FIELD_MAX - 40))))
    vals = [rng.choice(_ATOMS) for _ in range(n_leaves - n_text)]
    left = budget - sum(len(v) for v in vals)
    weights = [rng.random() + 0.5 for _ in range(n_text)]
    vals += [_text(rng, min(_FIELD_MAX, max(16, int(left * w / sum(weights))))) for w in weights]
    rng.shuffle(vals)
    if attack is not None:
        vals[rng.randrange(n_leaves)] = attack
    return vals


def json_body(rng, n_leaves, budget, attack) -> bytes:
    """Nested objects and arrays; ``ensure_ascii`` writes the
    vocabulary's non-ASCII letters as ``\\u`` escapes."""
    vals = _values(rng, n_leaves, budget, attack)
    doc: dict = {"nonce": SALT}
    keys = rng.sample(_KEYS, len(_KEYS))
    while vals:
        key = keys.pop()
        r = rng.random()
        if r < 0.55 or len(vals) < 2:
            v = vals.pop()
            doc[key] = int(v) if v.isdigit() and rng.random() < 0.5 else v
        elif r < 0.75:
            take = min(len(vals), rng.randrange(2, 5))
            doc[key] = [vals.pop() for _ in range(take)]
        elif r < 0.9:
            take = min(len(vals), rng.randrange(2, 4))
            doc[key] = {k: vals.pop() for k in rng.sample(_KEYS[:6], take)}
        else:
            take = min(len(vals), rng.randrange(2, 5))
            doc[key] = [{"name": vals.pop(), "active": rng.random() < 0.5, "parent": None}
                        for _ in range(take)]
    items = list(doc.items())
    rng.shuffle(items)
    return json.dumps(dict(items), ensure_ascii=True,
                      separators=rng.choice(((",", ":"), (", ", ": ")))).encode()


def form_body(rng, n_leaves, budget, attack) -> bytes:
    vals = _values(rng, n_leaves, budget, attack)
    pairs = [(k, v) for k, v in zip(rng.sample(_KEYS, len(vals)), vals)] + [("nonce", SALT)]
    rng.shuffle(pairs)
    return "&".join(f"{quote_plus(k)}={quote_plus(v)}" for k, v in pairs).encode()


def multipart_body(rng, n_parts, budget, attack, boundary: str) -> bytes:
    vals = _values(rng, n_parts, budget, attack)
    parts = [(k, v) for k, v in zip(rng.sample(_KEYS, len(vals)), vals)] + [("nonce", SALT)]
    rng.shuffle(parts)
    out = b""
    for k, v in parts:
        out += (f"--{boundary}\r\nContent-Disposition: form-data; name=\"{k}\"\r\n\r\n"
                .encode() + v.encode("utf-8") + b"\r\n")
    return out + f"--{boundary}--\r\n".encode()


def bodies_pool(spec: dict, payloads: list[str]) -> list[dict]:
    """``pool_requests`` requests (and ``pool_spare`` more, for those a
    salt can move) as wire bytes with ``SALT_TOKEN``."""
    rng = random.Random(spec["pool_seed"])
    salt_hex = spec["salt_hex"]
    grow = salt_hex - len(SALT)  # what one salt adds at send time
    lo, hi = spec["body_bytes"]["clip"]
    share = spec["content_types"]
    out = []
    for i in range(spec["pool_requests"] + spec["pool_spare"]):
        r = rng.random()
        kind = ("json" if r < share["json"] else
                "urlencoded" if r < share["json"] + share["urlencoded"] else "multipart")
        want = int(min(hi, max(lo, math.exp(
            rng.gauss(math.log(spec["body_bytes"]["median"]), spec["body_bytes"]["sigma"])))))
        attack = rng.choice(payloads) if rng.random() < spec["attack_share"] else None
        most = 4 if kind == "multipart" else 16
        n = min(most, max(rng.randrange(2, 5) if kind == "multipart" else rng.randrange(4, 17),
                          -(-want // _FIELD_MAX)))
        boundary = "----wafbench%016x" % rng.getrandbits(64)
        state = rng.getstate()
        budget, was_over, last = want, False, -1
        for _ in range(48):  # the same draw at another text budget until the length fits
            rng.setstate(state)
            if kind == "json":
                body, ctype = json_body(rng, n, budget, attack), "application/json"
            elif kind == "urlencoded":
                body = form_body(rng, n, budget, attack)
                ctype = "application/x-www-form-urlencoded"
            else:
                body = multipart_body(rng, n, budget, attack, boundary)
                ctype = f"multipart/form-data; boundary={boundary}"
            over = len(body) + grow - want
            if over > 0 and budget > 16 * n:
                budget, was_over = max(16 * n, budget - over - 2), True
            elif over >= -8 or was_over or over > 0 or len(body) == last:
                break  # fits, or as near as this draw gets
            else:
                budget, last = budget - over, len(body)
        if len(body) + grow > hi:
            raise SystemExit(f"request {i}: a {kind} body of {len(body) + grow} bytes")
        method = rng.choice(("POST", "POST", "POST", "PUT", "PATCH"))
        path = rng.choice(_PATHS).format(n=rng.randrange(1, 5000))
        headers = [
            ("Host", rng.choice(_HOSTS)), ("User-Agent", rng.choice(_AGENTS)),
            ("Accept", "application/json"), ("X-Request-Id", f"rq-{SALT}"),
            ("Content-Type", ctype),
            ("Content-Length", str(len(body) + grow)),
        ]
        head = f"{method} {path} HTTP/1.1\r\n" + "".join(f"{k}: {v}\r\n" for k, v in headers)
        out.append({"id": f"api-{i}", "wire": head.encode() + b"\r\n" + body, "kind": kind,
                    "body_bytes": len(body) + grow, "leaves": n, "attack": attack is not None})
    return out


# -- main ---------------------------------------------------------------------


def materialize(wire: bytes, salt: bytes):
    """A pool request with ``salt`` for its ``SALT_TOKEN``s, as the
    sidecar's frontend hands it to the engine (a GET's body dropped)."""
    from coraza_kubernetes_operator_tpu.sidecar import ingest

    head, _, body = wire.replace(SALT_TOKEN, salt).partition(b"\r\n\r\n")
    method, target, version, pairs, _sp = ingest._parse_head(head + b"\r\n\r\n")
    return ingest._materialize(
        method, target.decode("latin-1", "replace"), version, pairs,
        body if method != b"GET" else b"", b"127.0.0.1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_dir", type=Path)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this tool must not take a chip")

    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.tier_compile import spec_key
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine, warmup_request

    repo = Path(__file__).resolve().parents[2]
    cdir = args.config_dir.resolve()
    config = json.loads((cdir / "config.json").read_text())
    spec = json.loads((cdir / "freeze.json").read_text())
    salt_hex = spec["salt_hex"]
    text = read_rules(cdir / config["rules"])
    engine = WafEngine(text)
    if not engine._native.available:
        raise SystemExit("native tensorizer not loaded (set CKO_NATIVE_LIB): "
                         "the plan must be made by the tensorizer the sidecar uses")
    if engine.value_cache is None:
        raise SystemExit("value cache is a shipped default; engine has none")

    payloads = attack_payloads(repo / spec["payload_corpus"])
    raw = bodies_pool(spec, payloads)

    # -- reference verdicts, salt-invariant or left out -------------------------
    seeds = list(range(1, spec["salt_seeds"] + 1)) + [2**31 + 12345]
    by_seed = []
    for seed in seeds:
        reqs = [materialize(r["wire"], salt_for(seed, "freeze", i, salt_hex))
                for i, r in enumerate(raw)]
        by_seed.append([(v.status if v.interrupted else 200,
                         str(v.rule_id or 0) if v.interrupted else None)
                        for v in engine.host_fallback.evaluate(reqs)])
        print(f"reference verdicts, salt seed {seed}: done", file=sys.stderr)
    pool, moved = [], 0
    for i, r in enumerate(raw):
        got = {vs[i] for vs in by_seed}
        if len(got) != 1:
            moved += 1
            continue
        r["status"], r["rule_id"] = got.pop()
        pool.append(r)
    spare = len(pool) - spec["pool_requests"]
    if spare < 0:
        raise SystemExit(f"only {len(pool)} requests keep their verdict on every salt")
    del pool[spec["pool_requests"]:]

    # -- burst plans --------------------------------------------------------------
    serial = itertools.count(10**9)

    def build(i: int):
        return materialize(pool[i]["wire"], salt_for(0, "freeze", next(serial), salt_hex))

    def probe(reqs):
        """(the window's executables: one key per matcher and the post
        stage; matcher shapes; unique uncached rows a tier; their keys)."""
        tiers, numvals, masks, cached, miss_keys, lease = engine._batch_tensors(reqs)
        match_specs, post_spec, _pairs = engine._tier_specs(
            tiers, numvals, max_phase=2, masks=masks, cached=cached)
        if lease is not None:
            lease.release()
        sig = tuple(spec_key(s) for s in match_specs + [post_spec])
        post = [[int(t[5].shape[0]), 0 if c is None else int(c.shape[0])]
                for t, c in zip(tiers, cached)]  # row pairs, cached rows: the post stage's shape
        return (sig, [list(t[0].shape) for t in tiers], [len(k) for k in miss_keys], miss_keys,
                post)

    def remember(miss_keys) -> None:
        for keys in miss_keys:
            if keys:
                engine.value_cache.insert(
                    keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8))

    remember(probe([warmup_request()])[3])
    plans = {}
    for pname, p in spec["plans"].items():
        want = [list(s) for s in p["tier_shapes"]]
        size, long_over = p["burst_requests"], p["long_over"]
        lo_long, hi_long = p["long_per_burst"]
        is_long = [r["body_bytes"] > long_over for r in pool]
        rng = random.Random(p["plan_seed"])

        # prime: cold cache. Each burst is led by a long request, which
        # brings the window to the long width: the first by one that fits
        # cold, later ones by one already seen (it costs only its salted
        # rows). It grows, fewest fields first, while its rows fit the
        # wanted bucket, and is filled up to it with requests already seen.
        (rows_want, width_want), = want
        prime, seen, prime_sigs = [], [], set()
        todo = sorted(range(len(pool)), key=lambda i: (pool[i]["leaves"], pool[i]["body_bytes"]))

        def fits(shapes):
            return len(shapes) == 1 and shapes[0][1] == width_want and shapes[0][0] <= rows_want

        while todo:
            seen_long = [i for i in seen if is_long[i]]
            if seen_long:
                lead = seen_long[len(prime) % len(seen_long)]
            else:
                lead = next((i for i in todo if is_long[i] and fits(probe([build(i)])[1])), None)
                if lead is None:
                    raise SystemExit(f"{pname}: no long request lands on {want} cold")
                todo.remove(lead)
            idxs, built = [lead], [build(lead)]
            sig, shapes, n_miss, keys, post = probe(built)
            fill = itertools.cycle(seen or [lead])
            def attempt(i: int):
                one = build(i)
                grown = probe(built + [one])
                return (i, one, grown) if fits(grown[1]) else None

            while len(idxs) < p["prime_requests_max"] and (todo or shapes != want):
                # the next new request; where that is too much and the rows
                # are still short of the bucket, one already seen
                got = attempt(todo[0]) if todo else None
                fresh = got is not None
                if got is None and (not todo or shapes[0][0] < rows_want):
                    got = attempt(next(fill))
                if got is None:
                    break
                if fresh:
                    todo.pop(0)
                idxs.append(got[0])
                built.append(got[1])
                sig, shapes, n_miss, keys, post = got[2]
            if shapes != want:
                raise SystemExit(f"{pname}: prime burst {idxs} lands on {shapes}, not {want}")
            if not set(idxs) - set(seen):
                raise SystemExit(f"{pname}: request {todo[0]} fits no prime burst on {want}")
            remember(keys)
            seen += [i for i in idxs if i not in seen]
            prime_sigs.add(sig)
            prime.append({"lane": "bulk", "requests": idxs, "unique_uncached_rows": n_miss[0],
                          "tier_shapes": want, "post_shapes": post})

        # steady: every pool request once, ``size`` a burst, the long
        # ones dealt round the bursts; all on one set of executables.
        n_bursts = len(pool) // size
        longs = [i for i in range(len(pool)) if is_long[i]]
        shorts = [i for i in range(len(pool)) if not is_long[i]]
        if not lo_long * n_bursts <= len(longs) <= hi_long * n_bursts:
            raise SystemExit(f"{pname}: {len(longs)} long bodies for {n_bursts} bursts")
        # Balanced by rows: the post stage's executable is keyed by the
        # window's row-pair and cached-row buckets, so every burst gets
        # about the same number of rows (heaviest first, to the lightest
        # burst that still has room).
        def row_pairs(i: int) -> int:
            tiers, *_rest, lease = engine._batch_tensors([build(i)])
            n = sum(int((t[5] < 1).sum()) for t in tiers)
            if lease is not None:
                lease.release()
            return n

        weight = [row_pairs(i) for i in range(len(pool))]
        groups = [[] for _ in range(n_bursts)]
        rng.shuffle(longs)
        for k, i in enumerate(longs):
            groups[k % n_bursts].append(i)
        for i in sorted(shorts, key=lambda i: -weight[i]):
            room = [g for g in groups if len(g) < size]
            min(room, key=lambda g: sum(weight[j] for j in g)).append(i)
        for g in groups:
            rng.shuffle(g)
        probed = [probe([build(i) for i in g]) for g in groups]
        # What is left is the cached-row bucket, which follows how many
        # values a burst's requests share: bursts off the commonest set
        # trade one short request with a burst on it until none is off.
        for _ in range(4000):
            count: dict = {}
            for pr in probed:
                count[pr[0]] = count.get(pr[0], 0) + 1
            target = max(count, key=count.get)
            off = [k for k, pr in enumerate(probed) if pr[0] != target]
            if not off:
                break
            a = rng.choice(off)
            b = rng.choice([k for k in range(n_bursts) if k not in off])
            ia, ib = (rng.choice([i for i in groups[k] if not is_long[i]]) for k in (a, b))
            ga = [ib if i == ia else i for i in groups[a]]
            gb = [ia if i == ib else i for i in groups[b]]
            pa, pb = probe([build(i) for i in ga]), probe([build(i) for i in gb])
            if pb[0] == target and (pa[0] == target or pa[4] != probed[a][4] or rng.random() < 0.2):
                groups[a], groups[b], probed[a], probed[b] = ga, gb, pa, pb
        sigs = {pr[0] for pr in probed}
        if len(sigs) != 1 or any(pr[1] != want for pr in probed):
            raise SystemExit(f"{pname}: the steady bursts land on {len(sigs)} sets of "
                             f"executables, shapes {sorted({str(pr[1]) for pr in probed})}, row pairs and "
                             f"cached rows {sorted({str(pr[4]) for pr in probed})}")
        steady = [{"lane": "bulk", "requests": g, "unique_uncached_rows": pr[2][0],
                   "tier_shapes": pr[1], "post_shapes": pr[4], "long_bodies": sum(is_long[i] for i in g),
                   "wire_bytes": sum(len(pool[i]["wire"]) for i in g)}
                  for g, pr in zip(groups, probed)]
        # Replay with fresh salts: the composition must hold.
        again = [probe([build(i) for i in g]) for g in groups]
        assert [a[0] for a in again] == [pr[0] for pr in probed], "steady plan moved on replay"
        plans[pname] = {"tier_shapes": want, "prime": prime, "steady": steady,
                        "requests_per_pass": sum(len(g) for g in groups),
                        "left_out": {"unplaced": len(pool) - n_bursts * size}}
        print(f"plan {pname}: prime {len(prime)} bursts on {len(prime_sigs | sigs)} sets of "
              f"executables with the steady one, steady {len(steady)} bursts of {size}, "
              f"rows {sorted({b['unique_uncached_rows'] for b in steady})}, long bodies a burst "
              f"{sorted({b['long_bodies'] for b in steady})}, wire bytes a burst up to "
              f"{max(b['wire_bytes'] for b in steady)}", file=sys.stderr)

    with open(cdir / "corpus.jsonl", "w") as fh:
        for r in pool:
            fh.write(json.dumps({
                "id": r["id"], "wire": base64.b64encode(r["wire"]).decode(),
                "status": r["status"], "rule_id": r["rule_id"], "declared": [],
            }) + "\n")
    (cdir / "plans").mkdir(exist_ok=True)
    for pname, plan_ in plans.items():
        (cdir / "plans" / f"{pname}.json").write_text(json.dumps(plan_) + "\n")
    sizes = sorted(r["body_bytes"] for r in pool)
    kinds = [r["kind"] for r in pool]
    summary = {
        "pool_requests": len(pool),
        "blocked": sum(r["status"] != 200 for r in pool),
        "allowed": sum(r["status"] == 200 for r in pool),
        "by_content_type": {k: kinds.count(k) for k in ("json", "urlencoded", "multipart")},
        "carry_attack": sum(r["attack"] for r in pool),
        "attack_blocked": sum(r["attack"] and r["status"] != 200 for r in pool),
        "benign_blocked": sum(not r["attack"] and r["status"] != 200 for r in pool),
        "body_bytes": {"min": sizes[0], "median": sizes[len(sizes) // 2], "max": sizes[-1],
                       "over_1024": sum(s > 1024 for s in sizes)},
        "attack_payloads": len(payloads),
        "left_out": {"salt_moves_verdict": moved, "spare_unused": spare},
        "salt_seeds": seeds,
        "rules_compiled": len(engine.rule_meta),
        "rules_skipped": len(engine.compiled.report.skipped),
        "secrule_directives": sum(
            1 for ln in text.splitlines() if ln.lstrip().startswith("SecRule ")),
    }
    (cdir / "frozen.json").write_text(json.dumps(summary, indent=1) + "\n")
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
