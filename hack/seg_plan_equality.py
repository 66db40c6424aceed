"""Every plan of the conv tier against the direct conv, hit for hit, on the
device JAX finds (PR 43: ``chiprun -- python3 hack/seg_plan_equality.py``).

Tier-1 holds the same on the CPU (``tests/test_segment_column_tiles.py``,
whose feed and requests this borrows); a TPU multiplies in bf16 and plans
a tier past one chunk of tiles otherwise than the CPU does
(``models/waf_model.py:_scan_past_one_chunk``), so the chip gets a run of
its own: the 200-rule feed behind the sample, one ``128x128`` window, the
budget patched so that the tier takes row chunks, one chunk of tiles, row
chunks of tiles and the long scan in turn. One JSON line a plan; exit 1
if any differs from the direct conv.

PR 44: the same window with the unbounded class gaps as reachability
matmuls (``ops/segment.py:_REACH_MIN_ELEMS`` patched to 1: every structure)
against the latch's log-shift passes (patched out of reach), direct and in
one chunk of tiles: ``latch_against_matmul``, ``differing_cells`` 0.

PR 46: ``--cell <config>:<plan>`` (repeatable) holds the SERVED matcher
of a benchmark cell to the same: the configuration's rule text, the
plan's first steady burst salted and tensorized by the engine, its widest
tier (cut to the rows of the cell's steady window, the longest first) handed to ``cko_match_<rows>x<width>`` (``stage_executable``, the
sidecar's own program, ``mask`` None as ``hack/matcher_shape_probe.py``
compiles it, so the two share a persistent compile cache) under each of
``--budgets`` in turn; every plan's group hits against the last budget's.
``--budgets device`` is the budget the device's memory gives.

PR 47: the conv with its taps packed into the MXU's depth
(``ops/segment.py:conv_tap_packing``) against one tap a contraction (the
packing patched to 1): ``packed_against_plain`` on the feed window, direct
and in one chunk of tiles, and ``--conv plain packed`` on a cell's served
matcher (each form under each budget, all held to the last form under the
last budget; every line says its ``conv``)."""
import argparse
import base64
import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import jax
import numpy as np

from coraza_kubernetes_operator_tpu.engine import WafEngine
from coraza_kubernetes_operator_tpu.models import waf_model
from coraza_kubernetes_operator_tpu.ops import segment
from coraza_kubernetes_operator_tpu.ops.segment import conv_n2_cols, widest_group_cols
from wafbench.tools import freeze_custom


def feed_window() -> bool:
    spec = importlib.util.spec_from_file_location("tiles_tests", REPO / "tests/test_segment_column_tiles.py")
    T = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(T)

    feed = freeze_custom.feed_rules(T.N_FEED, T.SEED)
    engine = WafEngine(freeze_custom.feed_text(feed) + T.SAMPLE)
    tiers, _n, _m, _c, _k, lease = engine._batch_tensors(T._uri_requests(feed))
    tier = max(tiers, key=lambda t: t[0].shape[0] * t[0].shape[1])
    tier = tuple(np.array(tier[k]) for k in (0, 1, 6, 7))
    if lease is not None:
        lease.release()
    t, width = tier[0].shape
    q = width + 2
    n2 = sum(conv_n2_cols(s.spec) for s in engine.model.segs)
    widest = max(widest_group_cols(s.spec) for s in engine.model.segs)
    model = jax.device_put(engine.model)

    def hits(budget, scan=None):
        waf_model._SEG_CHUNK_ELEMS = budget
        if scan is not None:
            waf_model._scan_past_one_chunk = lambda: scan
        out = jax.jit(lambda m, *a: waf_model.match_tier(m, *a))(model, *tier)
        return np.asarray(out), waf_model.tier_seg_plan(engine.model, t, width).summary()

    direct, plan = hits(2**40)
    print(json.dumps({"shape": [t, width], "columns": n2, "widest": widest, "direct": plan,
                      "hits": int(direct.sum()), "cells": int(direct.size)}), flush=True)
    ok = True
    for name, budget, scan in (("rows", 16 * q * n2, None), ("tiles", 8 * q * n2 - 1, None),
                               ("tiles_x_rows_as_this_backend_plans", 8 * q * widest, None),
                               ("tiles_x_rows", 8 * q * widest, False), ("long", 8 * q * widest, True)):
        got, plan = hits(budget, scan)
        same = bool((got == direct).all())
        ok &= same
        print(json.dumps({"case": name, "plan": plan, "equal_to_direct": same,
                          "differing_cells": int((got != direct).sum())}), flush=True)
    for name, budget in (("direct", 2**40), ("tiles", 8 * q * n2 - 1)):
        got = {}
        for form, threshold in (("latch", 2**30), ("matmul", 1)):
            segment._REACH_MIN_ELEMS = threshold
            jax.clear_caches()  # match_segment_block's traces do not see the constant
            got[form] = hits(budget)
        same = bool((got["latch"][0] == got["matmul"][0]).all() and (got["latch"][0] == direct).all())
        ok &= same and got["latch"][1]["reach_gaps"] == 0 < got["matmul"][1]["reach_gaps"]
        print(json.dumps({"case": "latch_against_matmul", "plan": got["matmul"][1],
                          "reach_gaps": {f: g[1]["reach_gaps"] for f, g in got.items()},
                          "equal": same, "hits": int(got["matmul"][0].sum()),
                          "differing_cells": int((got["latch"][0] != got["matmul"][0]).sum())}), flush=True)
    packing = segment.conv_tap_packing
    for name, budget in (("direct", 2**40), ("tiles", 8 * q * n2 - 1)):
        got = {}
        for form, fn in (("plain", lambda spec: (1, spec.w)), ("packed", packing)):
            segment.conv_tap_packing = fn
            jax.clear_caches()  # match_segment_block's traces do not see the patch
            got[form] = hits(budget)
        same = bool((got["plain"][0] == got["packed"][0]).all() and (got["packed"][0] == direct).all())
        ok &= same and got["packed"][1]["conv_passes"] < got["plain"][1]["conv_passes"]
        print(json.dumps({"case": "packed_against_plain", "plan": got["packed"][1],
                          "conv_passes": {f: g[1]["conv_passes"] for f, g in got.items()},
                          "equal": same, "hits": int(got["packed"][0].sum()),
                          "differing_cells": int((got["plain"][0] != got["packed"][0]).sum())}), flush=True)
    # ... and the two forms alone at the feed's real sizes: rows of class runs of every length, a
    # tile's 300 columns, the positions of a 512 and of a 2,048 wide window.
    rng = np.random.default_rng(44)
    big = jax.numpy.int32(1 << 20)
    for rows, q, ns in ((16, 514, 1500), (32, 2050, 300)):
        outside = rng.random((rows, q)) > rng.choice([0.5, 0.9, 0.99, 0.999], (rows, 1))
        x = jax.numpy.asarray(rng.random((rows, q, ns)) < 0.002)
        nce = segment._excl_prefix_count(jax.numpy.asarray(outside))
        latch = segment._latch_min(jax.numpy.where(x, nce[..., None], big), big, forward=True) == nce[..., None]
        matmul = jax.jit(lambda x, nce: segment._reach_gap(x, segment._reach_tables(nce, big)))(x, nce)
        differing = int((np.asarray(latch) != np.asarray(matmul)).sum())
        ok &= differing == 0
        print(json.dumps({"case": "reach_gap_alone", "shape": [rows, q, ns], "set": int(np.asarray(latch).sum()),
                          "differing_cells": differing}), flush=True)
    return ok


def _budget(word: str) -> int | None:
    """``2**27``, ``134217728`` or ``device`` (None: no override)."""
    if word == "device":
        return None
    base, _, exp = word.partition("**")
    return int(base) ** int(exp) if exp else int(base)


def served_cells(cells: list[str], budgets: list[int | None], convs: list[str]) -> bool:
    from concurrent.futures import ThreadPoolExecutor

    from coraza_kubernetes_operator_tpu.engine.compile_cache import configure_persistent_cache
    from coraza_kubernetes_operator_tpu.models.slab import match_slab_shape, match_views
    from wafbench.generators.planned_bursts import salt_for
    from wafbench.harness import read_rules
    from wafbench.tools.freeze_bodies import materialize

    configure_persistent_cache()
    engines: dict[str, tuple] = {}
    packing = {"packed": segment.conv_tap_packing, "plain": lambda spec: (1, spec.w)}
    forms = [(conv, budget) for conv in convs for budget in budgets]
    lowered = []  # (cell, form, plan, operands, the lowering): traced one by one, under its form
    for cell in cells:
        config, plan = cell.split(":")
        cdir = REPO / "wafbench" / "configs" / config
        rules = cdir / "rules"
        text = read_rules(rules if rules.exists() else cdir / "rules.conf")
        same = text.replace(str(rules.resolve()), "")  # but for where its data files lie
        if same not in engines:  # two configurations may serve one text
            engine = WafEngine(text)
            engines[same] = engine, jax.device_put(engine.model)
        engine, model = engines[same]
        pool = [json.loads(line) for line in (cdir / "corpus.jsonl").open()]
        salt_hex = json.loads((cdir / "freeze.json").read_text())["salt_hex"]
        burst = json.loads((cdir / "plans" / f"{plan}.json").read_text())["steady"][0]
        reqs = [materialize(base64.b64decode(pool[i]["wire"]), salt_for(46, "equality", n, salt_hex))
                for n, i in enumerate(burst["requests"])]
        tiers, _n, _m, _c, _k, lease = engine._batch_tensors(reqs)
        tier = max(tiers, key=lambda t: t[0].shape[0] * t[0].shape[1])
        # The window the cell serves: steady, the value cache answers the rows seen
        # before, so a cold engine's tier may hold more rows than the served bucket;
        # the longest rows are kept.
        rows, width = max(burst["tier_shapes"], key=lambda s: s[0] * s[1])
        assert tier[0].shape[1] == width, (cell, tier[0].shape, width)
        kept = np.argsort(-tier[1], kind="stable")[:rows]
        slab = np.zeros(match_slab_shape(rows, width, tier[6].shape[0]), np.uint8)
        data, lengths, vdata, vlengths = match_views(slab)
        n = len(kept)
        data[:n], lengths[:n] = tier[0][kept], tier[1][kept]
        vdata[:, :n], vlengths[:, :n] = tier[6][:, kept], tier[7][:, kept]
        if lease is not None:
            lease.release()
        said = {"cell": cell, "shape": [rows, width], "requests": len(reqs),
                "rows_with_bytes": int((tier[1][kept] > 0).sum())}
        for conv, budget in forms:
            waf_model._SEG_CHUNK_ELEMS, segment.conv_tap_packing = budget, packing[conv]
            jax.clear_caches()  # a trace sees neither
            lowering = waf_model.stage_executable("match", f"{rows}x{width}").lower(model, slab, mask=None)
            lowered.append((said, (conv, budget), waf_model.tier_seg_plan(engine.model, rows, width).summary(),
                            (model, slab), lowering))
    # XLA releases the lock; two at a time: six compiles at once (three cells, two forms) met a one-chip
    # machine's 40 GiB (PR 47)
    with ThreadPoolExecutor(max_workers=2) as workers:
        compiled = list(workers.map(lambda job: job[4].compile(), lowered))
    ok = True
    # [U, PB] uint8, a bit a group (``np.packbits``): compared bit for bit, each plan of a
    # cell against the cell's last
    got = [np.unpackbits(np.asarray(run(*job[3])), axis=1) for job, run in zip(lowered, compiled)]
    for k, ((said, (conv, budget), plan, _operands, _lowering), out) in enumerate(zip(lowered, got)):
        want = got[k - k % len(forms) + len(forms) - 1]
        differing = int((out != want).sum())
        ok &= differing == 0
        print(json.dumps({**said, "conv": conv, "budget": budget if budget is not None else "device", "plan": plan,
                          "hits": int(out.sum()), "cells": int(out.size),
                          "differing_cells": differing}), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", action="append", default=[], help="<config>:<plan> of wafbench/configs")
    ap.add_argument("--budgets", nargs="+", type=_budget, default=[2**27, None],
                    help="conv-tier budgets to compare, the last the one the others are held to")
    ap.add_argument("--conv", nargs="+", choices=["plain", "packed"], default=["packed"],
                    help="the conv's forms to compare (--cell), the last the one the others are held to")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "memory_bytes_limit": (dev.memory_stats() or {}).get("bytes_limit"),
                      "scan_past_one_chunk": waf_model._scan_past_one_chunk()}), flush=True)
    ok = served_cells(args.cell, args.budgets, args.conv) if args.cell else feed_window()
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
