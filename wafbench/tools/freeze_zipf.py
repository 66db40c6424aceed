#!/usr/bin/env python3
"""Build-time tool: a repeat-traffic plan over a configuration's frozen
pool, for ``generators/zipf_repeat.py``.

    JAX_PLATFORMS=cpu CKO_NATIVE_LIB=build/wafbench/libcko_native.so \
        python3 -m wafbench.tools.freeze_zipf wafbench/configs/<name> <plan> \
        --repeat 128 --groups 16 --salted 16 --salt-hex 16

Reads ``corpus.jsonl`` as it is (nothing of the configuration is
edited) and writes ``plans/<plan>.json``: per lane the first
``--repeat`` pool requests as the repeat pool (the generator sends each
with one fixed salt; their reference verdict is the pool's, checked
here with the plain host evaluator on that salt), and from the requests
after them ``--groups`` groups of ``--salted``, each of which the
engine's own tensorizer, with a replica of its value cache, puts on
one set of executables when it rides a window alone, as it does once
every repeat around it is answered by the verdict cache. Never run by a
benchmark run.
"""

from __future__ import annotations

import argparse
import base64
import itertools
import json
import os
import sys
from pathlib import Path

from wafbench.generators.planned_bursts import SALT_TOKEN, salt_for
from wafbench.harness import read_rules
from wafbench.tools.freeze_bodies import materialize


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_dir", type=Path)
    ap.add_argument("plan")
    ap.add_argument("--repeat", type=int, default=128)
    ap.add_argument("--groups", type=int, default=16)
    ap.add_argument("--salted", type=int, default=16)
    ap.add_argument("--salt-hex", type=int, default=16)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this tool must not take a chip")

    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.tier_compile import spec_key
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine, warmup_request
    cdir = args.config_dir.resolve()
    config = json.loads((cdir / "config.json").read_text())
    engine = WafEngine(read_rules(cdir / config["rules"]))
    if not engine._native.available:
        raise SystemExit("native tensorizer not loaded (set CKO_NATIVE_LIB)")
    pool = [json.loads(line) for line in open(cdir / "corpus.jsonl")]
    wires = [base64.b64decode(r["wire"]) for r in pool]

    def lane_of(i: int) -> str:
        head, _, body = wires[i].partition(b"\r\n\r\n")
        return "bulk" if body and not head.startswith(b"GET ") else "interactive"

    serial = itertools.count(10**9)

    def probe(idxs):
        reqs = [materialize(wires[i], salt_for(0, "freeze", next(serial), args.salt_hex))
                for i in idxs]
        tiers, numvals, masks, cached, miss_keys, lease = engine._batch_tensors(reqs)
        match_specs, post_spec, _pairs = engine._tier_specs(
            tiers, numvals, max_phase=2, masks=masks, cached=cached)
        if lease is not None:
            lease.release()
        for keys in miss_keys:
            if keys:
                engine.value_cache.insert(
                    keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8))
        return (tuple(spec_key(s) for s in match_specs + [post_spec]),
                [list(t[0].shape) for t in tiers])

    probe_warm = engine._batch_tensors([warmup_request()])
    if probe_warm[5] is not None:
        probe_warm[5].release()
    plan = {"repeat": {}, "repeat_salt": "salt_for(0, 'repeat', <pool index>, salt_hex)",
            "steady": [], "tier_shapes": {}}
    for lane in ("interactive", "bulk"):
        mine = [i for i in range(len(pool)) if lane_of(i) == lane]
        repeat, rest = mine[:args.repeat], mine[args.repeat:]
        fixed = [materialize(wires[i], salt_for(0, "repeat", i, args.salt_hex)) for i in repeat]
        for i, v in zip(repeat, engine.host_fallback.evaluate(fixed)):
            got = (v.status if v.interrupted else 200,
                   str(v.rule_id or 0) if v.interrupted else None)
            if got != (pool[i]["status"], pool[i]["rule_id"]):
                raise SystemExit(f"pool request {i}: {got} on its fixed salt, not the pool's verdict")
        plan["repeat"][lane] = repeat
        probe(repeat)  # the prime pass: the repeat pool's values are cached from here on
        groups, want = [], None
        group: list[int] = []
        for i in rest:
            group.append(i)
            if len(group) == args.salted:
                probe(group)  # first sight: its unsalted values enter the value cache
                sig, shapes = probe(group)
                again, _ = probe(group)
                want = want or sig
                if sig == want == again:
                    groups.append((group, shapes))
                else:
                    print(f"{lane}: a group on {shapes} is left out", file=sys.stderr)
                group = []
            if len(groups) == args.groups:
                break
        if len(groups) < args.groups:
            raise SystemExit(f"{lane}: only {len(groups)} groups of {args.salted} on one set")
        plan["tier_shapes"][lane] = groups[0][1]
        plan["steady"] += [{"lane": lane, "requests": g, "tier_shapes": s} for g, s in groups]
        print(f"{lane}: repeat pool {len(repeat)}, {len(groups)} groups of {args.salted} on "
              f"{groups[0][1]}", file=sys.stderr)
    (cdir / "plans" / f"{args.plan}.json").write_text(json.dumps(plan) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
