#!/usr/bin/env python3
"""Build-time tool: freeze a configuration's request pool, reference
verdicts and burst plans into data files.

    JAX_PLATFORMS=cpu python -m wafbench.tools.freeze_config wafbench/configs/<name>

This is the ONLY file of wafbench that imports the program's engine. It
is never run by a benchmark run: it is run once, by the PR that adds a
configuration, on the CPU, and its outputs (``corpus.jsonl`` and
``plans/*.json``) are committed as data. It reads ``freeze.json`` in
the configuration's directory (where the requests come from and how the
bursts are sized) and

1. builds the request pool: every request as wire bytes with
   ``SALT_TOKEN`` wherever a per-send salt goes;
2. computes the reference verdict of every pool request with the plain
   host evaluator, on ``salt_seeds`` different salts, and keeps only
   requests whose verdict is the same on all of them (and, for a go-ftw
   corpus, whose status is the one the corpus itself declares): so the
   verdict of a pool request does not depend on its salt, and the table
   is the reference at run time;
3. plans bursts with the engine's own tensorizer and a replica of its
   cross-batch value cache, so that every burst is one window on one
   matcher shape, cold (``prime``) and steady.
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

from wafbench.generators.planned_bursts import SALT_TOKEN, salt_for as _salt_for
from wafbench.harness import read_rules

_FRAMING = {
    "content-length", "transfer-encoding", "connection", "expect",
    "x-cko-deadline-ms", "x-waf-tenant", "traceparent",
}


def salt_for(seed: int, serial: int, n_hex: int) -> bytes:
    """Salts as ``generators/planned_bursts.py`` makes them."""
    return _salt_for(seed, "freeze", serial, n_hex)


# -- request pools ------------------------------------------------------------


def ftw_pool(spec: dict, repo: Path):
    """(id, template wire bytes, declared statuses) from a go-ftw corpus."""
    from coraza_kubernetes_operator_tpu.ftw.loader import load_tests_report
    from coraza_kubernetes_operator_tpu.sidecar import ingest

    tests, unparsable = load_tests_report(repo / spec["corpus"])
    if unparsable:
        raise SystemExit(f"unparsable corpus files: {unparsable}")
    skipped = {"response_stage": 0, "framing": 0}

    def wire_safe(stage) -> bool:
        if stage.version != "HTTP/1.1" or not stage.uri or stage.uri != stage.uri.strip():
            return False
        if stage.method.encode() not in ingest._KNOWN_METHODS:
            return False
        if any(c.isspace() or ord(c) < 0x21 or c == "#" for c in stage.uri):
            return False
        if stage.uri.startswith(ingest.API_PREFIX):
            return False
        for k, v in stage.headers:
            if k.lower() in _FRAMING or not k or k != k.strip():
                return False
            if any(c in "\r\n\0" for c in k + v) or ":" in k:
                return False
        return True

    out = []
    for t in tests:
        for si, s in enumerate(t.stages):
            if s.response_status is not None:
                skipped["response_stage"] += 1
                continue
            if not wire_safe(s):
                skipped["framing"] += 1
                continue
            headers = list(s.headers)
            if not any(k.lower() == "host" for k, _ in headers):
                headers.insert(0, ("Host", "localhost"))
            if s.data:
                headers.append(("Content-Length", str(len(s.data))))
            uri = s.uri + ("&" if "?" in s.uri else "?") + spec["salt_arg"] + "="
            head = (f"{s.method} {uri}".encode("utf-8", "surrogateescape") + SALT_TOKEN
                    + b" HTTP/1.1\r\n"
                    + "".join(f"{k}: {v}\r\n" for k, v in headers).encode("utf-8", "surrogateescape")
                    + b"\r\n")
            out.append((f"{t.title}.{si}", head + s.data, list(s.status)))
    return out, skipped


# Copy of coraza_kubernetes_operator_tpu/corpus.py:synthetic_requests (PR 24),
# with the request's salt replaced by SALT_TOKEN so that every send differs.
_BENIGN_PATHS = [
    "/", "/index.html", "/api/v1/items", "/static/app.js", "/login",
    "/products?id=123&sort=asc", "/search?q=blue+widgets", "/health",
    "/api/users/42/profile", "/images/logo.png?v=2",
]
_ATTACK_QUERIES = [
    "/search?q=1%27%20UNION%20SELECT%20password%20FROM%20users--",
    "/item?id=1 or 1=1",
    "/page?x=<script>alert(1)</script>",
    "/view?f=../../../../etc/passwd",
    "/api?cmd=;cat /etc/passwd",
    "/q?a=sleep(10)",
    "/x?y=%3Cscript%20src=evil.js%3E",
    "/dl?f=php://filter/convert.base64-encode",
]
_UA_POOL = [
    f"Mozilla/5.0 ({os_}) {eng} {br}/{maj}.0.{b}"
    for os_ in (
        "X11; Linux x86_64",
        "Windows NT 10.0; Win64; x64",
        "Macintosh; Intel Mac OS X 10_15_7",
        "iPhone; CPU iPhone OS 17_4 like Mac OS X",
        "Android 14; Mobile",
    )
    for eng, br in (("AppleWebKit/537.36", "Chrome"), ("Gecko/20100101", "Firefox"))
    for maj, b in ((120, 6099), (121, 6167), (122, 6261), (123, 6312), (124, 6367))
]
_HOST_POOL = [
    "bench.local", "shop.bench.local", "api.bench.local", "cdn.bench.local",
    "admin.bench.local", "m.bench.local", "www.bench.local", "app.bench.local",
]


def synthetic_pool(spec: dict, _repo: Path):
    rng = random.Random(spec["pool_seed"])
    salt = SALT_TOKEN.decode()
    out = []
    for i in range(spec["pool_requests"]):
        attack = rng.random() < spec["attack_ratio"]
        base = rng.choice(_ATTACK_QUERIES if attack else _BENIGN_PATHS)
        # The wire cannot carry a raw space in the request target; the
        # program's own corpus hands HttpRequest objects to the engine,
        # a client on a socket percent-encodes.
        base = base.replace(" ", "%20")
        uri = f"{base}{'&' if '?' in base else '?'}_r={salt}"
        headers = [
            ("Host", rng.choice(_HOST_POOL)),
            ("User-Agent", rng.choice(_UA_POOL)),
            ("Accept", "*/*"),
            ("Cookie", f"session={salt}"),
        ]
        body = b""
        method = "GET"
        if rng.random() < spec["post_ratio"]:
            method = "POST"
            body = (f"field1=value{i % 64}&tok={salt}"
                    f"&field2={'benign+data+' * rng.randrange(1, 5)}").encode()
            headers.append(("Content-Type", "application/x-www-form-urlencoded"))
        out.append((f"syn-{i}", (method, uri, headers, body), []))
    return out, {}


def _synthetic_wire(req, salt_hex: int) -> bytes:
    method, uri, headers, body = req
    headers = list(headers)
    if body:
        # The body carries one salt: its length on the wire is fixed.
        n = len(body) - len(SALT_TOKEN) + salt_hex
        headers.append(("Content-Length", str(n)))
    head = f"{method} {uri} HTTP/1.1\r\n" + "".join(f"{k}: {v}\r\n" for k, v in headers) + "\r\n"
    return head.encode() + body


POOLS = {"ftw": ftw_pool, "synthetic": synthetic_pool}


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_dir", type=Path)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this tool must not take a chip")

    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine, warmup_request
    from coraza_kubernetes_operator_tpu.sidecar import ingest

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

    raw, skipped = POOLS[spec["pool"]](spec, repo)
    if spec["pool"] == "synthetic":
        raw = [(rid, _synthetic_wire(req, salt_hex), st) for rid, req, st in raw]
    for rid, wire, _ in raw:
        if wire.count(SALT_TOKEN) < 1:
            raise SystemExit(f"{rid}: no salt in the request")

    def materialize(wire: bytes, salt: bytes):
        data = wire.replace(SALT_TOKEN, salt)
        head, _, body = data.partition(b"\r\n\r\n")
        method, target, version, pairs, _sp = ingest._parse_head(head + b"\r\n\r\n")
        body = body if method != b"GET" else b""
        return ingest._materialize(
            method, target.decode("latin-1", "replace"), version, pairs, body, b"127.0.0.1"
        )

    # -- reference verdicts, salt-invariant or left out -------------------------
    seeds = list(range(1, spec["salt_seeds"] + 1)) + [2**31 + 12345]
    pool, left_out = [], dict(skipped, salt_moves_verdict=0, declared_status_differs=0)
    verdicts_by_seed = []
    for seed in seeds:
        reqs = [materialize(w, salt_for(seed, i, salt_hex)) for i, (_, w, _) in enumerate(raw)]
        vs = engine.host_fallback.evaluate(reqs)
        verdicts_by_seed.append(
            [(v.status if v.interrupted else 200,
              str(v.rule_id or 0) if v.interrupted else None) for v in vs]
        )
        print(f"reference verdicts, salt seed {seed}: done", file=sys.stderr)
    for i, (rid, wire, declared) in enumerate(raw):
        got = {vs[i] for vs in verdicts_by_seed}
        if len(got) != 1:
            left_out["salt_moves_verdict"] += 1
            continue
        status, rule_id = got.pop()
        if declared and status not in declared:
            left_out["declared_status_differs"] += 1
            continue
        pool.append({"id": rid, "wire": wire, "status": status, "rule_id": rule_id,
                     "declared": declared})

    # -- burst plans --------------------------------------------------------------
    serial = itertools.count(10**9)

    def build(i: int):
        return materialize(pool[i]["wire"], salt_for(0, next(serial), salt_hex))

    def probe(reqs):
        """(shape signature, unique uncached rows per tier, miss keys of tier 0)."""
        tiers, _nv, masks, _cached, miss_keys, lease = engine._batch_tensors(reqs)
        if lease is not None:
            lease.release()
        sig = tuple(
            (tuple(t[0].shape), masks[k] is not None) for k, t in enumerate(tiers)
        )
        return sig, [len(k) for k in miss_keys], miss_keys

    def remember(miss_keys) -> None:
        for keys in miss_keys:
            if keys:
                engine.value_cache.insert(
                    keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8)
                )

    def lane_of(i: int) -> str:
        head, _, body = pool[i]["wire"].partition(b"\r\n\r\n")
        return "bulk" if body and not head.startswith(b"GET ") else "interactive"

    remember(probe([warmup_request()])[2])
    plans = {}
    for pname, p in spec["plans"].items():
        # tier_shapes null: bursts are cut by max_requests alone and
        # the shapes they land on are recorded, not required.
        want_sig = p["tier_shapes"] and tuple((tuple(s), False) for s in p["tier_shapes"])
        lo, hi = p["miss_lo"], p["miss_hi"]
        too_wide = unplaced = 0
        usable = []
        for i in range(len(pool)):
            sig, _n, _k = probe([build(i)])
            if want_sig and (len(sig) != len(want_sig) or any(
                s[0][1] > w[0][1] for s, w in zip(sig, want_sig)
            )):
                too_wide += 1
            else:
                usable.append(i)

        def plan(order, fixed=None):
            bursts = []
            idxs, built, fill = [], [], [0, None, None]

            def fits(sig, n_miss):
                # While a burst grows only its widths must be the wanted
                # ones; its rows reach the wanted bucket before it closes.
                return (len(sig) == len(want_sig) and n_miss[0] <= hi and all(
                    s[0][1] == w[0][1] and s[1] == w[1] for s, w in zip(sig, want_sig)))

            def grow(i) -> bool:
                one = build(i)
                if want_sig:
                    sig, n_miss, keys = probe(built + [one])
                    if not fits(sig, n_miss):
                        return False
                    fill[:] = [n_miss[0], keys, sig]
                idxs.append(i)
                built.append(one)
                return True

            def close():
                if not want_sig:
                    sig, n_miss, keys = probe(built)
                    fill[:] = [n_miss[0], keys, sig]
                if want_sig and fill[2] != want_sig:
                    raise SystemExit(f"{pname}: burst {idxs} lands on {fill[2]}, not {want_sig}")
                remember(fill[1])
                bursts.append({"lane": lane_of(idxs[0]), "requests": list(idxs),
                               "unique_uncached_rows": fill[0],
                               "tier_shapes": [list(s[0]) for s in fill[2]]})
                del idxs[:], built[:]
                fill[:] = [0, None, None]

            if fixed is not None:
                for b in fixed:
                    for i in b["requests"]:
                        if not grow(i):
                            raise SystemExit(f"{pname}: a planned burst no longer fits: {b}")
                    close()
                return bursts
            nonlocal unplaced
            for lane in ("interactive", "bulk"):
                lane_idx = [i for i in order if lane_of(i) == lane]
                for i in lane_idx:
                    if not grow(i):
                        if idxs and fill[0] >= p["miss_min"]:
                            close()
                        if idxs or not grow(i):
                            unplaced += 1
                            continue
                    if (want_sig and fill[0] >= lo) or len(idxs) >= p.get("max_requests", 10**9):
                        close()
                placed = iter([i for b in bursts for i in b["requests"] if lane_of(i) == lane] * 64)
                while idxs and (not want_sig or fill[0] < lo) and len(idxs) < p.get("max_requests", 10**9):
                    if not grow(next(placed)):
                        break
                if idxs:
                    if want_sig and fill[0] < p["miss_min"]:
                        raise SystemExit(f"{pname}: last {lane} burst holds {fill[0]} rows")
                    close()
            return bursts

        prime = plan(usable)
        kept = [i for b in prime for i in b["requests"]]
        steady = plan(kept)
        # Replay the steady composition with fresh salts: it must hold.
        check = plan(kept, fixed=steady)
        assert [b["unique_uncached_rows"] for b in check] == [
            b["unique_uncached_rows"] for b in steady
        ], "steady plan moved on replay"
        plans[pname] = {
            "tier_shapes": p["tier_shapes"],
            "prime": prime,
            "steady": steady,
            "left_out": {"too_wide": too_wide, "unplaced": unplaced},
            "requests_per_pass": sum(len(b["requests"]) for b in steady),
        }
        print(f"plan {pname}: prime {len(prime)} bursts, steady {len(steady)} bursts, "
              f"{plans[pname]['requests_per_pass']} requests a pass, rows "
              f"{sorted({b['unique_uncached_rows'] for b in steady})}, shapes "
              f"{sorted({str(b['tier_shapes']) for b in steady})}, prime shapes "
              f"{sorted({str(b['tier_shapes']) for b in prime})}, left out "
              f"{plans[pname]['left_out']}", file=sys.stderr)

    with open(cdir / "corpus.jsonl", "w") as fh:
        for r in pool:
            fh.write(json.dumps({
                "id": r["id"], "wire": base64.b64encode(r["wire"]).decode(),
                "status": r["status"], "rule_id": r["rule_id"], "declared": r["declared"],
            }) + "\n")
    (cdir / "plans").mkdir(exist_ok=True)
    for pname, plan_ in plans.items():
        (cdir / "plans" / f"{pname}.json").write_text(json.dumps(plan_) + "\n")
    report = engine.compiled.report
    summary = {
        "pool_requests": len(pool),
        "blocked": sum(r["status"] != 200 for r in pool),
        "allowed": sum(r["status"] == 200 for r in pool),
        "left_out": left_out,
        "salt_seeds": seeds,
        "rules_compiled": len(engine.rule_meta),
        "rules_skipped": len(report.skipped),
        "secrule_directives": sum(
            1 for ln in text.splitlines() if ln.lstrip().startswith("SecRule ")
        ),
    }
    (cdir / "frozen.json").write_text(json.dumps(summary, indent=1) + "\n")
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
