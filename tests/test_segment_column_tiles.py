"""The conv tier cut over columns as well as rows (ISSUE 43).

``segment_tier_hits`` chooses per traced shape, from shapes alone, among
four plans: direct, row chunks, column tiles x row chunks, the long DFA
scan (``plan_segment_tier``). On a 200-rule feed cut from
``wafbench/tools/freeze_custom.py``'s four templates (the path patches,
26-byte literals, are PR 40's 24 + 2 byte chained pieces) and seeded rows,
with the budget patched small, every plan gives the direct conv's hits
column for column, with ``keep`` subsets and with a split group at a
tile's edge; the plan never says ``long`` while a tile of one row fits,
except that a backend that is no TPU scans a tier whose rows do not fit
one chunk of tiles; ten times the columns at the same budget still tile.
Engine
level: JSON, urlencoded and multipart bodies whose fields trip templates
b and d get the host evaluator's verdicts, rule id included, on the tiled
plan, and the executable cache says which plan each matcher was traced
with.

ISSUE 44: ``seg_plan.reach_gaps`` counts the unbounded class gaps a
launch runs as reachability matmuls (``ops/segment.py:_reach_gap``): 0
for crs-lite at every shape, the hand count on a spec with wide
structures, direct and tiled; and with the threshold patched to 1 the
feed's window gives the latch's group hits and verdicts.
"""

from __future__ import annotations

import json
import random
from pathlib import Path

import jax
import numpy as np
import pytest

from coraza_kubernetes_operator_tpu.models import waf_model
from coraza_kubernetes_operator_tpu.ops import segment as seg_mod
from coraza_kubernetes_operator_tpu.ops.segment import (
    conv_n2_cols,
    cut_column_tiles,
    tile_spec,
    widest_group_cols,
)
from wafbench.tools import freeze_custom

REPO = Path(__file__).resolve().parents[1]
SAMPLE = (REPO / "wafbench" / "configs" / "operator-sample" / "rules.conf").read_text()
N_FEED, SEED = 200, 37


@pytest.fixture(scope="module")
def feed():
    return freeze_custom.feed_rules(N_FEED, SEED)


@pytest.fixture(scope="module")
def engine(feed):
    from coraza_kubernetes_operator_tpu.engine import WafEngine

    with pytest.MonkeyPatch.context() as mp:
        for k in ("CKO_AUTOMATA", "CKO_NATIVE"):
            mp.delenv(k, raising=False)
        return WafEngine(freeze_custom.feed_text(feed) + SAMPLE)


def _value(rule: dict, near: bool) -> str:
    t = list(rule["tokens"])
    if near:
        t[-1] = freeze_custom.near_miss(t[-1])
    return f"{t[0]} ( '{t[1]}" if rule["template"] == "b" else f"{t[1]}_9z=v{t[3]}"


def _bodied(rule: dict, near: bool, kind: str):
    """A bodied request whose one field trips ``rule`` (template b or d),
    or its near-miss."""
    from urllib.parse import quote_plus

    from coraza_kubernetes_operator_tpu.engine import HttpRequest

    value = _value(rule, near)
    if kind == "json":
        ctype, body = "application/json", json.dumps(
            {"order": {"note": value, "qty": 3}, "tags": ["blue", "large"]}).encode()
    elif kind == "urlencoded":
        ctype = "application/x-www-form-urlencoded"
        body = f"qty=3&note={quote_plus(value)}&tag=blue".encode()
    else:
        ctype = "multipart/form-data; boundary=----cko43"
        body = (b"------cko43\r\nContent-Disposition: form-data; name=\"qty\"\r\n\r\n3\r\n"
                b"------cko43\r\nContent-Disposition: form-data; name=\"note\"\r\n\r\n"
                + value.encode() + b"\r\n------cko43--\r\n")
    return HttpRequest(method="POST", uri="/api/v1/orders",
                       headers=[("Host", "api.local"), ("User-Agent", "okhttp/4.12.0"),
                                ("Content-Type", ctype), ("Content-Length", str(len(body)))],
                       body=body)


def _uri_requests(feed) -> list:
    """Hits and near-misses of every template in the URI and the agent,
    and seeded noise."""
    from coraza_kubernetes_operator_tpu.engine import HttpRequest

    rng = random.Random(43)
    reqs = []
    for rule in rng.sample(feed, 24):
        for near in (False, True):
            t = list(rule["tokens"])
            if near:
                t[-1] = freeze_custom.near_miss(t[-1])
            agent, uri = "Mozilla/5.0 Firefox/115.0", "/app/view?page=2"
            if rule["template"] == "a":
                uri = f"/{t[0]}/{t[1]}/{t[2]}.php?page=2"
            elif rule["template"] == "b":
                uri = f"/app/view?q={t[0]}%20(%20'{t[1]}"
            elif rule["template"] == "c":
                agent = f"{t[0]}/12.5"
            else:
                uri = f"/app/view?ref={t[1]}_9z%3Dv{t[3]}"
            reqs.append(HttpRequest(method="GET", uri=uri,
                                    headers=[("Host", "localhost"), ("User-Agent", agent)]))
    for i in range(16):
        noise = "".join(rng.choice("abcdefghij/=._( ") for _ in range(rng.randrange(4, 90)))
        reqs.append(HttpRequest(method="GET", uri=f"/n{i}?v={noise}",
                                headers=[("Host", "localhost"), ("User-Agent", noise[:40])]))
    return reqs


@pytest.fixture(scope="module")
def tier(engine, feed):
    """The widest tier of one window of the requests above."""
    tiers, _numvals, _masks, _cached, _miss, lease = engine._batch_tensors(_uri_requests(feed))
    tier = max(tiers, key=lambda t: t[0].shape[0] * t[0].shape[1])
    held = tuple(np.array(tier[k]) for k in (0, 1, 6, 7))
    if lease is not None:
        lease.release()
    assert held[0].shape[0] >= 32
    return held


def _hits(engine, tier, monkeypatch, budget: int, mask=None, as_on_a_tpu: bool = False):
    """(group hits of ``match_tier`` under ``budget``, the plan it traces);
    ``as_on_a_tpu`` plans a tier past one chunk of tiles as a TPU does
    (row chunks of tiles) and not as this CPU (the long scan)."""
    monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", budget)
    monkeypatch.setattr(waf_model, "_scan_past_one_chunk", lambda: not as_on_a_tpu)
    out = jax.jit(lambda m, *a: waf_model.match_tier(m, *a, mask=mask))(engine.model, *tier)
    return np.asarray(out), waf_model.tier_seg_plan(engine.model, *tier[0].shape, mask)


def _shape(engine, tier):
    t, width = tier[0].shape
    n2 = sum(conv_n2_cols(s.spec) for s in engine.model.segs)
    widest = max(widest_group_cols(s.spec) for s in engine.model.segs)
    return t, width + 2, n2, widest


def test_every_plan_gives_the_direct_convs_hits_column_for_column(engine, tier, monkeypatch):
    t, q, n2, widest = _shape(engine, tier)
    direct, plan = _hits(engine, tier, monkeypatch, 2**40)
    assert plan.path == "direct" and plan.summary()["columns"] == n2
    assert direct.any() and not direct.all()
    rows, plan = _hits(engine, tier, monkeypatch, 16 * q * n2)
    assert plan.path == "rows" and plan.rows_per_chunk == 16 and plan.row_chunks == -(-t // 16)
    assert (rows == direct).all()
    # eight rows of all columns just do not fit: all rows in one chunk, the
    # columns in as many tiles as there are eights of rows
    t8 = -(-t // 8) * 8
    one_chunk, plan = _hits(engine, tier, monkeypatch, 8 * q * n2 - 1)
    got = plan.summary()
    assert got["path"] == "tiles" and got["row_chunks"] == 1 and got["rows_per_chunk"] == t8
    assert got["column_tiles"] >= t8 // 8 and got["columns_per_tile_max"] <= 8 * n2 // t8
    assert (one_chunk == direct).all()
    # eight rows of the widest group and no more: tiles x row chunks on a TPU,
    # the long scan on any other backend
    scanned, plan = _hits(engine, tier, monkeypatch, 8 * q * widest)
    assert plan.path == "long" and (scanned == direct).all()
    both, plan = _hits(engine, tier, monkeypatch, 8 * q * widest, as_on_a_tpu=True)
    got = plan.summary()
    assert got["path"] == "tiles" and got["rows_per_chunk"] == 8 and got["row_chunks"] == -(-t // 8)
    assert got["column_tiles"] >= 10
    assert (both == direct).all()
    # a tile's edge between two path patches, each PR 40's two chained pieces
    edges = {(i, g0) for i, g0, _g1, _c in plan.tiles if g0}
    split = [(i, g0) for i, g0 in edges
             if any(len(prog) == 2 and all(el[0] == "seg" for el in prog)
                    for gid, prog, _s, _e in engine.model.segs[i].spec.branches if gid == g0)]
    assert split, "no tile starts on a split group"


def test_keep_subsets_tile_to_the_same_hits(engine, tier, monkeypatch):
    n_segs = len(engine.model.segs)
    widest_block = max(range(n_segs), key=lambda i: conv_n2_cols(engine.model.segs[i].spec))
    t, q, _n2, _widest = _shape(engine, tier)
    for mask in ((1 << widest_block) | ~((1 << n_segs) - 1) & (2**62 - 1), 2**62 - 1 - 1):
        direct, plan = _hits(engine, tier, monkeypatch, 2**40, mask=mask)
        assert plan.path == "direct"
        kept = sum(conv_n2_cols(engine.model.segs[i].spec)
                   for i in range(n_segs) if mask >> i & 1)
        widest = max(widest_group_cols(engine.model.segs[i].spec)
                     for i in range(n_segs) if mask >> i & 1)
        tiled, plan = _hits(engine, tier, monkeypatch, -(-t // 8) * 8 * q * widest, mask=mask)
        assert plan.path == "tiles" and plan.columns == kept and len(plan.tiles) > 2
        assert {i for i, *_ in plan.tiles} == {i for i in range(n_segs) if mask >> i & 1}
        assert (tiled == direct).all()


def test_tiles_are_cut_along_group_boundaries_and_cover_the_block(engine):
    spec = max((s.spec for s in engine.model.segs), key=conv_n2_cols)
    whole = conv_n2_cols(spec)
    for max_cols in (widest_group_cols(spec), 40, whole // 3, whole, 10 * whole):
        tiles = cut_column_tiles(spec, max_cols)
        assert [g0 for g0, _g1, _c in tiles] == [0] + [g1 for _g0, g1, _c in tiles[:-1]]
        assert tiles[-1][1] == spec.n_groups
        for g0, g1, cols in tiles:
            sub = tile_spec(spec, g0, g1)
            assert conv_n2_cols(sub) == cols and sub.n_groups == g1 - g0
            assert cols <= max(max_cols, widest_group_cols(spec))
        # every branch in exactly one tile, its group id counted from the tile's first
        assert sum(len(tile_spec(spec, g0, g1).branches) for g0, g1, _ in tiles) \
            == len(spec.branches)
    assert cut_column_tiles(spec, 10 * whole) == [(0, spec.n_groups, whole)]
    assert tile_spec(spec, 0, spec.n_groups) is spec


def _specs(n_rules: int):
    """The conv tier's specs of a feed of ``n_rules`` behind the sample,
    planned and built as ``build_model`` does, no engine."""
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
    from coraza_kubernetes_operator_tpu.models.waf_model import build_model

    text = freeze_custom.feed_text(freeze_custom.feed_rules(n_rules, SEED)) + SAMPLE
    return [s.spec for s in build_model(compile_rules(text)).segs]


def _executables(engine) -> list[dict]:
    """``compile_cache.executables`` of this engine's model alone: the
    cache is the process's, and another test's engine may compile into it."""
    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE

    mine = format(hash(engine._model_sig) & 0xFFFFFFFF, "08x")
    return [e for e in EXEC_CACHE.stats()["executables"] if e["model"] == mine]


def test_the_plan_follows_from_shapes(engine, monkeypatch):
    specs = [s.spec for s in engine.model.segs]
    keep = tuple(range(len(specs)))
    n2 = sum(conv_n2_cols(s) for s in specs)
    widest = max(widest_group_cols(s) for s in specs)
    t, width = 32, 2048
    q = width + 2

    def plan(budget, specs=specs, keep=keep, t=t, width=width, long_ok=True, scan=False):
        monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", budget)
        return waf_model.plan_segment_tier(specs, keep, t, width, long_ok, scan)

    assert plan(t * q * n2).path == "direct"
    assert plan(t * q * n2 - 1).path == "rows"
    assert plan(8 * q * n2).summary() == {
        "path": "rows", "row_chunks": 4, "rows_per_chunk": 8, "column_tiles": len(keep),
        "columns_per_tile_max": max(conv_n2_cols(s) for s in specs), "columns": n2,
        "budget_elements": 8 * q * n2, "reach_gaps": 0,
        "conv_passes": sum(seg_mod.conv_passes(s) for s in specs),
        "conv_fill": round(sum(conv_n2_cols(s) * seg_mod.conv_fill(s) for s in specs) / n2, 4)}
    # one element short of eight rows of everything: tiles, all rows in one chunk,
    # down to the budget that holds all rows of the widest group and no more
    for budget in (8 * q * n2 - 1, t * q * widest):
        for scan in (False, True):
            got = plan(budget, scan=scan)
            assert (got.path, got.row_chunks, got.rows_per_chunk) == ("tiles", 1, t), budget
            assert t * q * max(c for *_, c in got.tiles) <= budget
    # the rows do not fit one chunk of tiles: row chunks of tiles, the most rows a
    # chunk that hold the widest group, down to one: never the long scan while a
    # tile fits, with long banks or without
    for budget, rows in ((t * q * widest - 1, 16), (8 * q * widest, 8), (8 * q * widest - 1, 4),
                         (2 * q * widest, 2), (q * widest, 1)):
        for long_ok in (True, False):
            got = plan(budget, long_ok=long_ok)
            assert (got.path, got.rows_per_chunk) == ("tiles", rows), budget
            assert got.rows_per_chunk * q * max(c for *_, c in got.tiles) <= budget
            assert got.row_chunks * got.rows_per_chunk >= t
        # ... but where the backend is no TPU and long banks were built, the scan
        assert plan(budget, scan=True).path == "long", budget
        assert plan(budget, long_ok=False, scan=True).path == "tiles", budget
    # one row of the widest group does not fit: the long scan, if it was built
    assert plan(q * widest - 1).path == plan(q * widest - 1, scan=True).path == "long"
    assert plan(q * widest - 1, long_ok=False).path == "direct"
    # a tier twice as wide at the same budget is more tiles, not another path
    assert plan(t * 2 * q * widest, width=2 * width).path == "tiles"
    assert plan(8 * q * widest, width=2 * width).path == "tiles"


def test_ten_times_the_columns_at_the_same_budget_still_tile(engine, monkeypatch):
    specs = [s.spec for s in engine.model.segs]
    n2 = sum(conv_n2_cols(s) for s in specs)
    budget = 8 * 258 * n2 - 1  # the 200-rule feed just tiles at 32 x 256
    monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", budget)
    small = waf_model.plan_segment_tier(specs, tuple(range(len(specs))), 32, 256, True)
    big_specs = _specs(10 * N_FEED)
    big_n2 = sum(conv_n2_cols(s) for s in big_specs)
    assert big_n2 > 8 * n2
    big = waf_model.plan_segment_tier(big_specs, tuple(range(len(big_specs))), 32, 256, True)
    assert small.path == big.path == "tiles"
    # ten times the feed: its block is dealt into as many more tiles
    assert [i for i, *_ in big.tiles].count(0) >= 8 > [i for i, *_ in small.tiles].count(0)
    assert all(big.rows_per_chunk * 258 * c <= budget for *_, c in big.tiles)
    assert sum(g1 - g0 for _i, g0, g1, _c in big.tiles) == sum(s.n_groups for s in big_specs)


def test_bodies_that_trip_the_feed_get_the_host_verdicts_on_the_tiled_plan(
        engine, feed, monkeypatch):
    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE

    rng = random.Random(44)
    picked = (rng.sample([r for r in feed if r["template"] == "b"], 3)
              + rng.sample([r for r in feed if r["template"] == "d"], 3))
    reqs, want = [], []
    for rule, kind in zip(picked, ("json", "urlencoded", "multipart") * 2):
        for near in (False, True):
            reqs.append(_bodied(rule, near, kind))
            want.append((200, None) if near else (403, rule["id"]))
    host = [(v.status if v.interrupted else 200, v.rule_id if v.interrupted else None)
            for v in engine.host_fallback.evaluate(reqs)]
    assert host == want
    n2 = sum(conv_n2_cols(s.spec) for s in engine.model.segs)
    # the window is 32 rows of 256: a sixth of the columns a tile
    monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", 32 * 258 * (n2 // 6))
    EXEC_CACHE.clear()
    jax.clear_caches()
    try:
        before = engine.tiering_summary()["long_scan_launches"]
        got = [(v.status if v.interrupted else 200, v.rule_id if v.interrupted else None)
               for v in engine.evaluate(reqs)]
        plans = [e["seg_plan"] for e in _executables(engine) if e["name"].startswith("cko_match_")]
        assert engine.tiering_summary()["long_scan_launches"] == before
    finally:
        EXEC_CACHE.clear()
        jax.clear_caches()
    assert got == host
    assert plans and all(p["path"] == "tiles" and p["column_tiles"] > 6 for p in plans)


def test_what_is_counted_says_which_plan_ran(engine, feed, tier, monkeypatch):
    """``seg_plan`` on every resident matcher executable and none on the
    post stage; ``cko.seg.tile`` in the registry and on the tiled
    program's operations (the CPU's compiler folds the barrier away and
    fuses the concatenation into its consumer, so the name is looked for
    where the program is traced; a v5e keeps 5 operations under it in
    custom5k's ``32x2048`` matcher); ``automata.segment_long_groups``; and
    ``tiering.long_scan_launches`` growing by the launches of an
    executable traced onto the long scan, and by nothing else."""
    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE
    from coraza_kubernetes_operator_tpu.observability import device_scopes

    assert "cko.seg.tile" in device_scopes.SCOPES and len(device_scopes.SCOPES) == 18
    auto = engine.automata_summary()
    assert auto["segment_long_groups"] == auto["tiers"]["segment"] > N_FEED
    reqs = _uri_requests(feed)[:12]
    n2 = sum(conv_n2_cols(s.spec) for s in engine.model.segs)

    def served(budget: int):
        monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", budget)
        EXEC_CACHE.clear()
        jax.clear_caches()
        before = engine.tiering_summary()
        verdicts = [(v.status if v.interrupted else 200, v.rule_id) for v in engine.evaluate(reqs)]
        after = engine.tiering_summary()
        listed = _executables(engine)
        grew = {k: after[k] - before[k] for k in ("tiers", "long_scan_launches")}
        return verdicts, listed, grew

    try:
        tiled, listed, grew = served(32 * 66 * (n2 // 6))  # windows of 32 rows of 64
        _t, q, _n2, _widest = _shape(engine, tier)
        monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", 8 * q * n2 - 1)
        traced = jax.jit(lambda m, *a: waf_model.match_tier(m, *a)).lower(
            engine.model, *tier).as_text(debug_info=True)
        assert "cko.seg.tile" in traced and "optimization_barrier" in traced
        matchers = [e for e in listed if e["name"].startswith("cko_match_")]
        assert matchers and grew["long_scan_launches"] == 0 < grew["tiers"]
        for e in listed:
            if e["name"].startswith("cko_match_"):
                assert set(e["seg_plan"]) == {"path", "row_chunks", "rows_per_chunk",
                                              "column_tiles", "columns_per_tile_max", "columns",
                                              "budget_elements", "reach_gaps", "conv_passes",
                                              "conv_fill"}
                assert e["seg_plan"]["path"] == "tiles" and e["seg_plan"]["columns"] == n2
                assert e["seg_plan"]["budget_elements"] == 32 * 66 * (n2 // 6)  # what it was cut to
                assert set(e["device_ops"]["by_scope"]) <= set(device_scopes.SCOPES)
            else:
                assert e["seg_plan"] is None  # the post stage traces no conv tier
        long, listed, grew = served(1)
        assert {e["seg_plan"]["path"] for e in listed if e["name"].startswith("cko_match_")} \
            == {"long"}
        assert grew["long_scan_launches"] == grew["tiers"] > 0
        assert all(e["seg_plan"]["row_chunks"] * e["seg_plan"]["column_tiles"] == 0
                   for e in listed if e["name"].startswith("cko_match_"))
    finally:
        EXEC_CACHE.clear()
        jax.clear_caches()
    assert tiled == long and any(status == 403 for status, _rule in tiled)


# -- the class gaps that ride the MXU (ISSUE 44) ----------------------------------------------


def _wide_spec(n_rules: int):
    """``n_rules`` parameter signatures (two unbounded class gaps, one
    structure of ``n_rules`` columns) and as many spaced pairs (one, under
    another), group by group in turn: a column tile of k rules holds k
    columns of each structure."""
    from coraza_kubernetes_operator_tpu.compiler.re_parser import parse_regex
    from coraza_kubernetes_operator_tpu.compiler.segments import plan_segments

    pats = [p for i in range(n_rules)
            for p in (rf"zq{i:03d}k\s*\(\s*['\"]?wv{i:03d}j", rf"zq{i:03d}kx\s*wv{i:03d}j")]
    return seg_mod.build_segment_block(
        [plan_segments(parse_regex(p, case_insensitive=False)) for p in pats]).spec


def test_reach_gaps_is_the_hand_count_direct_and_tiled(monkeypatch):
    """The threshold patched to a block of 8 rows x 64 positions x 32
    columns: a structure counts by the elements of its block as the plan's
    chunk traces it, rows x positions x its columns in the tile."""
    monkeypatch.setattr(seg_mod, "_REACH_MIN_ELEMS", 8 * 64 * 32)
    spec = _wide_spec(80)
    n2, width = conv_n2_cols(spec), 62
    assert seg_mod.reach_gap_count(spec, 8, 64) == 3
    assert seg_mod.reach_gap_count(_wide_spec(31), 8, 64) == 0
    assert seg_mod.reach_gap_count(_wide_spec(31), 16, 64) == 3  # twice the rows: half the columns do
    assert seg_mod.reach_gap_count(spec, 8, 24) == 0

    def plan(budget, t):
        monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", budget)
        return waf_model.plan_segment_tier([spec], (0,), t, width, long_ok=False)

    for budget, path in ((2**40, "direct"), (16 * 64 * n2, "rows")):
        got = plan(budget, 32)
        assert (got.path, got.summary()["reach_gaps"]) == (path, 3)  # a lax.map's body counts once
    halves = plan(8 * 64 * (n2 // 2 + 4), 8)  # two tiles of 40 rules: both structures wide in each
    assert halves.path == "tiles" and len(halves.tiles) == 2 and halves.summary()["reach_gaps"] == 6
    thirds = plan(8 * 64 * (n2 // 3 + 4), 8)  # 27 rules a tile: no block reaches 8 x 64 x 32
    assert len(thirds.tiles) == 3 and thirds.summary()["reach_gaps"] == 0
    uneven = plan(8 * 64 * (n2 * 9 // 20), 8)  # 36 + 36 + 8 rules
    assert [g1 - g0 for _i, g0, g1, _c in uneven.tiles] == [72, 72, 16]
    assert uneven.summary()["reach_gaps"] == 6
    # the count is the sum over the tiles of what each tile's own spec traces
    assert uneven.reach_gaps == sum(
        seg_mod.reach_gap_count(tile_spec(spec, g0, g1), 8, 64) for _i, g0, g1, _c in uneven.tiles)
    monkeypatch.setattr(seg_mod, "_REACH_MIN_ELEMS", 32 * 64 * 81)
    assert plan(2**40, 32).reach_gaps == 0


def test_reach_gaps_is_zero_for_crs_lite_at_every_served_shape():
    """crs-lite's widest suffix structure is 16 columns (ISSUE 44 read it
    from the model): its block is a sixth of the threshold at ``32x2048``,
    so every crs-lite and crs-bodies matcher that is served keeps the
    latch under every plan; only a chunk of 64 rows or more at 8,192 bytes
    (no window of any cell) holds a block of theirs that large."""
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
    from coraza_kubernetes_operator_tpu.models.waf_model import build_model
    from wafbench.harness import read_rules

    text = read_rules(REPO / "wafbench" / "configs" / "crs-lite-pl2" / "rules")
    specs = [s.spec for s in build_model(compile_rules(text)).segs]
    widest = max(len(members) for spec in specs
                 for members in seg_mod._suffix_structures(spec)[1].values())
    assert widest == 16 and 32 * 2050 * widest < seg_mod._REACH_MIN_ELEMS
    keep = tuple(range(len(specs)))
    plans = {(rows, width, scan): waf_model.plan_segment_tier(specs, keep, rows, width, True, scan)
             for rows in (8, 16, 32, 64, 256) for width in (32, 128, 512, 2048, 8192)
             for scan in (False, True)}
    assert {p.path for p in plans.values()} == {"direct", "rows", "tiles", "long"}
    for (rows, width, scan), p in plans.items():
        large = p.rows_per_chunk * (width + 2) * widest >= seg_mod._REACH_MIN_ELEMS
        assert p.summary()["reach_gaps"] == 0 or large, (rows, width, scan)
        assert not large or (width == 8192 and p.rows_per_chunk >= 64)
    assert plans[64, 8192, False].reach_gaps == 2  # the 16- and a 12-column structure, one gap each


def test_the_feeds_window_with_every_gap_on_the_mxu_gives_the_latchs_hits_and_verdicts(
        engine, feed, tier, monkeypatch):
    """Threshold patched to 1: every unbounded forward class gap of every
    structure of the 200-rule feed and the sample takes the matmul form
    (XLA:CPU would never choose it: it is slower there), direct and in
    column tiles; group hits and verdicts are the unpatched run's."""
    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE

    t, q, n2, _widest = _shape(engine, tier)
    reqs = _uri_requests(feed)

    def run(threshold):
        monkeypatch.setattr(seg_mod, "_REACH_MIN_ELEMS", threshold)
        EXEC_CACHE.clear()
        jax.clear_caches()  # match_segment_block's traces do not see the constant
        direct, plan = _hits(engine, tier, monkeypatch, 2**40)
        tiled, tiles = _hits(engine, tier, monkeypatch, 8 * q * n2 - 1)
        monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", 2**40)
        verdicts = [(v.status if v.interrupted else 200, v.rule_id) for v in engine.evaluate(reqs)]
        counted = [e["seg_plan"]["reach_gaps"] for e in _executables(engine)
                   if e["name"].startswith("cko_match_")]
        return direct, tiled, verdicts, (plan.reach_gaps, tiles.reach_gaps, counted)

    try:
        latch = run(seg_mod._REACH_MIN_ELEMS)
        reach = run(1)
    finally:
        EXEC_CACHE.clear()
        jax.clear_caches()
    assert latch[3][:2] == (0, 0) and latch[3][2] and not any(latch[3][2])
    assert reach[3][0] >= 3 and reach[3][1] >= reach[3][0] and all(reach[3][2])
    assert (reach[0] == latch[0]).all() and (reach[1] == latch[1]).all()
    assert (latch[0] == latch[1]).all() and latch[0].any()
    assert reach[2] == latch[2] and any(status == 403 for status, _rule in reach[2])


# -- the taps that fill the MXU's depth (ISSUE 47) ---------------------------------------------


def test_conv_passes_and_fill_are_the_hand_count_direct_and_tiled(monkeypatch):
    """``seg_plan.conv_passes`` / ``conv_fill``: every tile's conv makes its
    block's taps (W a block with one tap a contraction, ``ceil(W / (128 //
    C))`` packed), each one 128-deep slice while k·C fits the depth, and
    fills k·C of the 128, weighted by the tiles' columns; the long scan
    makes no conv."""
    spec = _wide_spec(80)
    w, c, n2, width = spec.w, len(spec.channels), conv_n2_cols(spec), 62
    k = 128 // c
    assert k > 1 and seg_mod.conv_tap_packing(spec) == (k, -(-w // k))

    def plan(budget, t=8, long_ok=False):
        monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", budget)
        return waf_model.plan_segment_tier([spec], (0,), t, width, long_ok=long_ok)

    for budget, tiles in ((2**40, 1), (8 * 64 * (n2 // 2 + 4), 2), (8 * 64 * (n2 // 3 + 4), 3)):
        said = plan(budget).summary()
        assert said["column_tiles"] == tiles
        assert said["conv_passes"] == tiles * -(-w // k) and said["conv_fill"] == round(k * c / 128, 4)
    long = plan(1, t=1, long_ok=True)
    assert long.path == "long" and (long.conv_passes, long.conv_fill) == (0, 0.0)
    monkeypatch.setattr(seg_mod, "conv_tap_packing", lambda s: (1, s.w))  # one tap a contraction
    said = plan(8 * 64 * (n2 // 2 + 4)).summary()
    assert said["conv_passes"] == 2 * w and said["conv_fill"] == round(c / 128, 4)


def test_crs_lites_taps_fill_the_depth_at_every_served_shape(monkeypatch):
    """What ISSUE 47 read from the model by hand: crs-lite's eight blocks
    make 170 passes of the MXU's depth an output tile with one tap a
    contraction, at 22 to 42 of its 128 rows, and 47 with the taps packed,
    at 104 to 128; a plan changes neither, rows or tiles."""
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
    from coraza_kubernetes_operator_tpu.models.waf_model import build_model
    from wafbench.harness import read_rules

    text = read_rules(REPO / "wafbench" / "configs" / "crs-lite-pl2" / "rules")
    specs = [s.spec for s in build_model(compile_rules(text)).segs]
    keep = tuple(range(len(specs)))
    assert sorted((s.w, len(s.channels)) for s in specs) == [
        (11, 16), (15, 22), (19, 27), (24, 26), (24, 42), (25, 27), (26, 27), (26, 36)]
    assert [seg_mod.conv_tap_packing(s) for s in sorted(specs, key=lambda s: (s.w, len(s.channels)))] == [
        (8, 2), (5, 3), (4, 5), (4, 6), (3, 8), (4, 7), (4, 7), (3, 9)]

    def said(rows, width):
        return waf_model.plan_segment_tier(specs, keep, rows, width, True, False).summary()

    for rows, width in ((32, 512), (512, 512), (32, 2048), (1, 64)):
        got = said(rows, width)
        assert got["path"] in ("direct", "rows") and (got["conv_passes"], got["conv_fill"]) == (47, 0.8715)
    monkeypatch.setattr(seg_mod, "conv_tap_packing", lambda s: (1, s.w))
    got = said(32, 512)
    assert (got["conv_passes"], got["conv_fill"]) == (170, 0.2617)
