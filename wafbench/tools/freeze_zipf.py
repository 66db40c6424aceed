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
every repeat around it is answered by the verdict cache.

    ... freeze_zipf wafbench/configs/crs-lite-pl2 ftw-repeat80 --repeat 48 --salt-hex 300 \
        --steady-from ftw-salted --prime-groups

``--steady-from <plan>`` takes the steady groups of a plan that is there
instead of cutting new ones, and the lanes' requests from them (what
that plan left out as too wide stays out); each group is checked again
to ride that plan's ``tier_shapes`` alone. ``--prime-groups`` (with
``--steady-from``, whose entry in ``freeze.json`` gives the rows a
window may hold) writes a ``prime`` list: each lane's repeat pool and
then the rest of its requests, every one on its fixed salt, cut into
groups that each land on the same shapes from a cold value cache, in
the order a run sends them. Without it the generator's prime pass sends
a lane's repeat pool whole, as one window of whatever shape that makes.
Never run by a benchmark run.
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


def remember(engine, miss_keys) -> None:
    """Cache a probed window's rows, as serving it would."""
    import numpy as np

    for keys in miss_keys:
        if keys:
            engine.value_cache.insert(
                keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8))


def from_plan(args, cdir, engine, pool, wires, lane_of, probe) -> dict:
    """The plan for ``--steady-from``: that plan's steady groups, each
    lane's first ``--repeat`` of their requests as its repeat pool, and
    with ``--prime-groups`` the prime pass cut to the same shapes."""
    from coraza_kubernetes_operator_tpu.engine.value_cache import ValueHitCache
    from coraza_kubernetes_operator_tpu.engine.waf import warmup_request

    source = json.loads((cdir / "plans" / f"{args.steady_from}.json").read_text())
    want = source["tier_shapes"]
    rows_of = json.loads((cdir / "freeze.json").read_text())["plans"][args.steady_from]
    lo, hi, least = rows_of["miss_lo"], rows_of["miss_hi"], rows_of["miss_min"]

    def fixed(i: int):
        return materialize(wires[i], salt_for(0, "repeat", i, args.salt_hex))

    def place(reqs, keep: bool):
        """(tier shapes, unique uncached rows of the first tier) of one
        window of ``reqs``; ``keep`` caches its rows as a served window does."""
        tiers, _nv, _masks, _cached, miss_keys, lease = engine._batch_tensors(reqs)
        if lease is not None:
            lease.release()
        if keep:
            remember(engine, miss_keys)
        return [list(t[0].shape) for t in tiers], len(miss_keys[0])

    def cold():
        """The value cache as a sidecar has it before its first request."""
        engine.value_cache = ValueHitCache(engine.value_cache.packed_len,
                                           engine.value_cache.max_bytes)
        place([warmup_request()], keep=True)

    def replay(groups):
        """Rows and shapes of ``groups`` sent in order from a cold cache."""
        cold()
        return [place([fixed(i) for i in g], keep=True) for g in groups]

    lanes, repeat, order = ("interactive", "bulk"), {}, {}
    for lane in lanes:
        mine = sorted({i for g in source["steady"] if g["lane"] == lane for i in g["requests"]})
        if any(lane_of(i) != lane for i in mine):
            raise SystemExit(f"{args.steady_from}: a {lane} group holds another lane's request")
        repeat[lane] = mine[:args.repeat]
        order[lane] = repeat[lane] + mine[args.repeat:]
        for i, v in zip(repeat[lane], engine.host_fallback.evaluate(
                [fixed(i) for i in repeat[lane]])):
            got = (v.status if v.interrupted else 200,
                   str(v.rule_id or 0) if v.interrupted else None)
            if got != (pool[i]["status"], pool[i]["rule_id"]):
                raise SystemExit(f"pool request {i}: {got} on its fixed salt, not the pool's verdict")

    plan = {"repeat": repeat, "repeat_salt": "salt_for(0, 'repeat', <pool index>, salt_hex)",
            "steady_from": args.steady_from, "tier_shapes": want,
            "left_out": dict(source.get("left_out", {}), steady_groups_off_shape=0)}
    if args.prime_groups:
        widths = [w[1] for w in want]
        groups: list[tuple[str, list[int]]] = []
        cold()
        for lane in lanes:
            done = [g for _lane, g in groups]
            mine: list[list[int]] = []
            group: list[int] = []
            for i in order[lane]:
                shapes, n = place([fixed(k) for k in group + [i]], keep=False)
                if group and (n > hi or [s[1] for s in shapes] != widths):
                    place([fixed(k) for k in group], keep=True)
                    mine.append(group)
                    group = []
                    shapes, n = place([fixed(i)], keep=False)
                group.append(i)
                if n >= lo:
                    place([fixed(k) for k in group], keep=True)
                    mine.append(group)
                    group = []
            if group:
                # A short tail borrows from the group before it until both
                # hold the rows of the wanted bucket.
                before, tail = (mine[-1] if mine else []), group
                for k in range(max(1, len(before))):
                    trial = mine[:-1] + [before[:len(before) - k], before[len(before) - k:] + tail]
                    trial = [g for g in trial if g]
                    if all(shapes == want and least <= n <= hi
                           for shapes, n in replay(done + trial)[len(done):]):
                        mine = trial
                        break
                else:
                    raise SystemExit(f"{lane}: the last prime group {tail} fits no bucket of {want}")
            groups += [(lane, g) for g in mine]
        plan["prime"] = []
        for (lane, g), (shapes, n) in zip(groups, replay([g for _lane, g in groups])):
            if shapes != want or not least <= n <= hi:
                raise SystemExit(f"prime group {g} lands on {shapes} with {n} rows, not {want}")
            plan["prime"].append({"lane": lane, "requests": g, "unique_uncached_rows": n,
                                  "tier_shapes": shapes})
        placed = {i for _lane, g in groups for i in g}
        plan["left_out"]["unplaced"] = sum(len(set(order[lane]) - placed) for lane in lanes)
        print(f"prime: {len(groups)} groups, rows "
              f"{sorted({g['unique_uncached_rows'] for g in plan['prime']})}", file=sys.stderr)
    else:
        cold()
        for lane in lanes:
            place([fixed(i) for i in order[lane]], keep=True)

    # Steady groups as the run sends them: fresh salts, every unsalted
    # value seen before, the repeats around them answered by the verdict cache.
    plan["steady"] = []
    sigs = set()
    for g in source["steady"]:
        sig, shapes, rows = probe(g["requests"])
        again, _shapes, _rows = probe(g["requests"])
        if shapes != want or sig != again:
            plan["left_out"]["steady_groups_off_shape"] += 1
            print(f"{g['lane']}: group {g['requests']} on {shapes} is left out", file=sys.stderr)
            continue
        sigs.add(sig)
        plan["steady"].append({"lane": g["lane"], "requests": g["requests"],
                               "unique_uncached_rows": rows[0], "tier_shapes": shapes})
    # One set of matchers; the post stage (cheap to compile, and warmed by
    # the run's warm rounds) may differ by its buckets of pairs and cached rows.
    if len({sig[:-1] for sig in sigs}) != 1:
        raise SystemExit("the steady groups ride more than one set of matcher executables")
    plan["post_stage_variants"] = len(sigs)
    print(f"steady: {len(plan['steady'])} of {len(source['steady'])} groups on {want}, "
          f"{len(sigs)} post stage variants, rows "
          f"{sorted({g['unique_uncached_rows'] for g in plan['steady']})}; repeat pools "
          f"{ {lane: len(r) for lane, r in repeat.items()} }", file=sys.stderr)
    return plan


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config_dir", type=Path)
    ap.add_argument("plan")
    ap.add_argument("--repeat", type=int, default=128)
    ap.add_argument("--groups", type=int, default=16)
    ap.add_argument("--salted", type=int, default=16)
    ap.add_argument("--salt-hex", type=int, default=16)
    ap.add_argument("--steady-from", metavar="PLAN")
    ap.add_argument("--prime-groups", action="store_true")
    args = ap.parse_args(argv)
    if args.prime_groups and not args.steady_from:
        raise SystemExit("--prime-groups needs --steady-from: its shapes are the groups' shapes")
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this tool must not take a chip")

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
        remember(engine, miss_keys)
        return (tuple(spec_key(s) for s in match_specs + [post_spec]),
                [list(t[0].shape) for t in tiers], [len(k) for k in miss_keys])

    if args.steady_from:
        plan = from_plan(args, cdir, engine, pool, wires, lane_of, probe)
        (cdir / "plans" / f"{args.plan}.json").write_text(json.dumps(plan) + "\n")
        return 0
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
                sig, shapes, _rows = probe(group)
                again, _shapes, _rows = probe(group)
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
