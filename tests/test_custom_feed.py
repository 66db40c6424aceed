"""A site's own ``@rx`` feed beside its base rules (wafbench's
``crs-lite-pl2-custom5k``, ISSUE 37), at a size the CPU compiles.

The feed comes from the generator the configuration was frozen with
(``wafbench/tools/freeze_custom.py:feed_rules``): four templates, every
rule its own tokens. What grows with it is pinned here at 600 rules: the
device engine against the plain host evaluator (exact status and rule
id, first and last rule of the feed, a near-miss of every template),
with more than 512 groups in the model and bins of more than 256 slots
(the boundaries PR 31's slot digits tripped on: the path patches,
26-byte literals, ride the conv tier as two chained pieces, so the bin
is filled by template e, 40 patches whose directory may repeat — an
unbounded repetition of a composite, which no segment plan holds);
the finals tier of
``ops/segment.py`` batched over one structure's suffixes; the flat
planner at the widths the engine launches a bin at; the hot-tier bank
packing in linear time. JAX-free at the end: the cell resolves by name
through ``wafbench.harness`` and deploys ``crs-lite-pl2``'s argv.
"""

from __future__ import annotations

import json
import re
import time
from pathlib import Path

import numpy as np
import pytest

from wafbench import harness
from wafbench.tools import freeze_custom

REPO = Path(__file__).resolve().parents[1]
CONFIGS = REPO / "wafbench" / "configs"
NEW = CONFIGS / "crs-lite-pl2-custom5k"
SAMPLE = (CONFIGS / "operator-sample" / "rules.conf").read_text()
N_FEED, SEED = 600, 37
TEMPLATES = "abcd"
N_DENSE, DENSE_BASE_ID = 40, 9100000


def _dense_rules(taken: set) -> list[dict]:
    """Template e: ``(?i:/(?:<tok6>/)+<tok5>\\.php)``, 19 DFA states each."""
    import random

    rng = random.Random(38)
    rules = []
    for i in range(N_DENSE):
        t = freeze_custom._tokens(rng, taken, 6, 5)
        rules.append({"id": DENSE_BASE_ID + i, "template": "e", "variable": "REQUEST_URI",
                      "pattern": rf"(?i:/(?:{t[0]}/)+{t[1]}\.php)",
                      "transforms": "t:none,t:urlDecodeUni", "tokens": t})
    return rules


@pytest.fixture(scope="module")
def feed():
    return freeze_custom.feed_rules(N_FEED, SEED)


@pytest.fixture(scope="module")
def dense(feed):
    return _dense_rules({tok for r in feed for tok in r["tokens"]})


@pytest.fixture(scope="module")
def engine(feed, dense):
    from coraza_kubernetes_operator_tpu.engine import WafEngine

    with pytest.MonkeyPatch.context() as mp:
        for k in ("CKO_AUTOMATA", "CKO_NATIVE"):
            mp.delenv(k, raising=False)
        return WafEngine(freeze_custom.feed_text(feed + dense) + SAMPLE)


def _request(rule: dict, near: bool):
    """A request ``rule`` blocks, or its near-miss (one byte of its last
    token changed)."""
    from coraza_kubernetes_operator_tpu.engine import HttpRequest

    t = list(rule["tokens"])
    if near:
        t[-1] = freeze_custom.near_miss(t[-1])
    headers = [("Host", "localhost"), ("User-Agent", "Mozilla/5.0 Firefox/115.0")]
    uri = "/app/view?page=2"
    if rule["template"] == "a":
        uri = f"/{t[0]}/{t[1]}/{t[2]}.php?page=2"
    elif rule["template"] == "e":
        uri = f"/{t[0]}/{t[0]}/{t[1]}.php?page=2"
    elif rule["template"] == "b":
        uri = f"/app/view?q={t[0]}%20(%20'{t[1]}"
    elif rule["template"] == "c":
        headers[1] = ("User-Agent", f"{t[0]}/12.5")
    else:
        uri = f"/app/view?ref={t[1]}_9z%3Dv{t[3]}"
    return HttpRequest(method="GET", uri=uri, headers=headers)


def _verdict(v) -> tuple:
    return (v.status if v.interrupted else 200, v.rule_id if v.interrupted else None)


def test_the_feed_is_seeded_and_its_tokens_are_distinct(feed):
    assert feed == freeze_custom.feed_rules(N_FEED, SEED)
    assert feed != freeze_custom.feed_rules(N_FEED, SEED + 1)
    assert feed == freeze_custom.feed_rules(5000, SEED)[:N_FEED]  # a cut feed is a prefix
    tokens = [tok for r in feed for tok in r["tokens"]]
    assert len(tokens) == len(set(tokens)) and all(t.isalpha() and t.islower() for t in tokens)
    shares = {tpl: sum(r["template"] == tpl for r in feed) for tpl in TEMPLATES}
    assert shares == {"a": 240, "b": 180, "c": 120, "d": 60}
    assert [r["id"] for r in feed] == list(range(9000000, 9000000 + N_FEED))
    text = freeze_custom.feed_text(feed)
    assert text.count("\nSecRule ") == N_FEED and text == freeze_custom.feed_text(feed)


def test_picks_hold_the_ends_of_the_feed_and_four_of_every_template():
    for n in (200, N_FEED, 5000):
        got = freeze_custom.picks(n)
        assert len(got) == len(set(got)) == 23
        assert {0, 9, n - 1} <= set(got)  # first rule, first and last of template d
        by = {tpl: sum(freeze_custom.TEMPLATE_OF[i % 10] == tpl for i in got)
              for tpl in TEMPLATES}
        assert min(by.values()) >= 4, by


def test_the_feed_layout_is_the_parents(engine):
    """The layout as the parent of PR 48 built it, before the per-bank
    matchers went (``tests/data/layout_pins.json``, by its SHA-256)."""
    from conftest import layout_pin, layout_pin_sha256

    pins = json.loads((REPO / "tests" / "data" / "layout_pins.json").read_text())
    assert layout_pin_sha256(layout_pin(engine.model)) == pins["custom-feed"]["sha256"]
    assert engine.model.banks == [] and [b.kind for b in engine.model.dense_blocks] == ["dfa-hot"]


def test_the_model_is_past_the_boundaries(engine):
    auto = engine.automata_summary()
    assert auto["rules"] == N_FEED + N_DENSE + 2 == len(engine.rule_meta)
    assert len(engine.compiled.groups) > 512
    assert auto["per_bank_kernels"] == 0
    assert max(fb.n_slots for fb in engine.model.flat_banks) > 256
    assert auto["flat_slots"] == sum(fb.n_slots for fb in engine.model.flat_banks)
    from coraza_kubernetes_operator_tpu.ops.segment import conv_n2_cols

    assert auto["segment_columns"] == sum(conv_n2_cols(s.spec) for s in engine.model.segs) > 512
    # all four templates are in the conv tier, the path patches (26 bytes
    # is past the conv's MAX_SEG_LEN) as two chained pieces each; what
    # fills the bins is the 40 rules no segment plan holds
    assert auto["tiers"]["segment"] == N_FEED + 2 and auto["tiers"]["dfa-hot"] == N_DENSE
    assert auto["segment_split_groups"] == auto["segment_splits"] == 240
    assert auto["flat_groups"] == N_DENSE


def test_device_verdicts_equal_the_host_evaluators(engine, feed, dense):
    first_of = {tpl: next(r for r in feed if r["template"] == tpl) for tpl in TEMPLATES}
    last_of = {tpl: next(r for r in reversed(feed) if r["template"] == tpl) for tpl in TEMPLATES}
    rules = [feed[0], feed[-1], *first_of.values(), *last_of.values(), feed[255], feed[256],
             feed[257], feed[511], feed[512], feed[513], dense[0], dense[13], dense[14], dense[-1]]
    reqs, want = [], []
    for r in rules:
        reqs += [_request(r, near=False), _request(r, near=True)]
        want += [(403, r["id"]), (200, None)]
    from coraza_kubernetes_operator_tpu.engine import HttpRequest

    reqs.append(HttpRequest(method="GET", uri="/?q=1%27%20union%20select%20a%20from%20b",
                            headers=[("Host", "localhost")]))
    host = [_verdict(v) for v in engine.host_fallback.evaluate(reqs)]
    assert host[:-1] == want, "the reference does not say what the generator promises"
    assert host[-1][0] == 403 and host[-1][1] not in range(9000000, 9005000)
    device = [_verdict(v) for v in engine.evaluate(reqs)]
    assert device == host
    # and one by one, where a request's rows sit first in their tiers
    for k in (0, 1, 2, 3):
        assert _verdict(engine.evaluate([reqs[k]])[0]) == host[k]


def test_finals_of_one_structure_are_batched_not_one_op_a_suffix(engine):
    """Every b and d rule brings a suffix of its own; the matcher's
    program must not grow with them."""
    import jax

    from coraza_kubernetes_operator_tpu.ops.segment import match_segment_block

    seg = max(engine.model.segs, key=lambda s: len(s.spec.branches))
    assert len(seg.spec.branches) > 300
    data = jax.ShapeDtypeStruct((8, 64), np.uint8)
    lengths = jax.ShapeDtypeStruct((8,), np.int32)
    text = match_segment_block.lower(seg.kernel, seg.spec, data, lengths).as_text()
    assert text.count("stablehlo.case") + text.count("stablehlo.if") < 40
    assert text.count("\n") < 6000


def _dfas_of_27_states(n: int):
    from coraza_kubernetes_operator_tpu.compiler import compile_regex_dfa

    rules = [r for r in freeze_custom.feed_rules(n * 10 // 4, SEED) if r["template"] == "a"][:n]
    dfas = [compile_regex_dfa(r["pattern"]) for r in rules]
    assert {d.n_states for d in dfas} == {27}
    return dfas


@pytest.mark.parametrize("width", [512, 2048])
def test_flat_planner_keeps_every_bin_inside_the_chips_budget(width):
    """500 dfa-hot DFAs of 27 states (the feed's path patches scanned as
    DFAs: what a feed of small rules with no segment plan is): no block
    rejected, every bin inside the scoped-VMEM limit the chip's compiler
    enforces, at the widths a bin is launched at."""
    from coraza_kubernetes_operator_tpu.ops import dfa_flat

    dfas = _dfas_of_27_states(500)
    blocks = [(i, 0, dfas[i * 125:(i + 1) * 125]) for i in range(4)]
    bins, rejected = dfa_flat.plan_flat_bins(blocks, length_hint=width)
    assert not rejected
    assert sum(hi - lo for bn in bins for _b, _p, lo, hi, _d in bn) == 500
    for bn in bins:
        slots, groups, tbytes, pipes = dfa_flat._layout_stats(bn)
        assert dfa_flat.flat_vmem_bytes(slots, groups, tbytes, width, pipes) \
            <= dfa_flat._FLAT_VMEM_BUDGET < dfa_flat.CHIP_SCOPED_VMEM_BYTES
        assert slots <= dfa_flat.MAX_BIN_SLOTS


def test_the_refusal_the_chip_recorded_is_over_the_planners_budget():
    """A 3,584-slot bin over two pipelines at width 2,048 was refused by
    the v5e's compiler at 16.09 MB of 16.00: the estimator must say so."""
    from coraza_kubernetes_operator_tpu.ops import dfa_flat

    tables = 256 * 3584 * 2
    est = dfa_flat.flat_vmem_bytes(3584, 128, tables, 2048, 2)
    assert est > dfa_flat._FLAT_VMEM_BUDGET
    assert est >= 16.0e6  # not under what the compiler counted


def test_hot_tier_bank_packing_is_linear_in_the_feed():
    from coraza_kubernetes_operator_tpu.compiler.automata_plan import (
        _HOT_MAX_JOINT_CLASSES,
        cut_hot_blocks,
    )
    from coraza_kubernetes_operator_tpu.compiler.re_dfa import joint_class_count

    dfas = _dfas_of_27_states(1000)
    t0 = time.monotonic()
    blocks = cut_hot_blocks(dfas)
    assert time.monotonic() - t0 < 4.0  # restacking every block per candidate: 14 s at 1,000
    assert sorted(i for b in blocks for i in b) == list(range(1000))
    for b in blocks:
        assert joint_class_count([dfas[i] for i in b]) <= _HOT_MAX_JOINT_CLASSES


# -- install: what a feed adds to a reload (PR 37) ---------------------------------------------


def test_shadowing_walks_a_feed_in_seconds_and_still_finds_the_shadowed_rule(feed):
    """5,000 deny rules over one pipeline took the reload gate's
    shadowing check over ten minutes pair by pair (the harness gives a
    sidecar 300 s to load); an earlier pattern now scans every later
    rule's shortest match in one pass, and only what passes reaches the
    DFA product."""
    from coraza_kubernetes_operator_tpu.analysis.rulelint import analyze_ruleset

    shadowed = (  # the first path patch again, narrower, and a near-miss of it that is not
        f'SecRule REQUEST_URI "@rx (?i:/{"/".join(feed[0]["tokens"][:2])}/'
        f'{feed[0]["tokens"][2]}\\.php\\?x)" "id:9900001,phase:2,deny,status:403,'
        't:none,t:urlDecodeUni"\n'
        f'SecRule REQUEST_URI "@rx (?i:/{"/".join(feed[0]["tokens"][:2])}/zz\\.php)" '
        '"id:9900002,phase:2,deny,status:403,t:none,t:urlDecodeUni"\n')
    t0 = time.monotonic()
    report = analyze_ruleset(freeze_custom.feed_text(feed) + shadowed + SAMPLE)
    assert time.monotonic() - t0 < 60  # pair by pair: 180k products, minutes
    r004 = [f for f in report.findings if f.code == "CKO-R004"]
    assert [(f.rule_id, "9000000" in f.message) for f in r004] == [(9900001, True)]


def test_the_shadowing_filter_agrees_with_the_product_on_every_pair():
    """The filter only ever drops pairs the product would refuse."""
    from coraza_kubernetes_operator_tpu.analysis import rulelint
    from coraza_kubernetes_operator_tpu.compiler import compile_regex_dfa

    pats = ["abc", "ab", "abcd", "b", "a[0-9]+c", "a1c", "x*", "(?:ab|cd)e", "cde", "zz$", "zz"]
    dfas = [compile_regex_dfa(p) for p in pats]
    words = [rulelint._shortest_match(d) for d in dfas]
    assert all(w is not None and d.search(w) for w, d in zip(words, dfas))
    assert [len(w) for w in words] == [3, 2, 4, 1, 3, 3, 0, 3, 3, 2, 2]
    order = sorted(range(len(pats)), key=lambda i: -len(words[i]))
    padded = np.zeros((len(pats), 4), np.uint8)
    for row, i in enumerate(order):
        padded[row, :len(words[i])] = np.frombuffer(words[i], np.uint8)
    lengths = np.array([len(words[i]) for i in order])
    live = [int((lengths > j).sum()) for j in range(4)]
    for big in dfas:
        got = rulelint._matches_each(big, padded, live)
        assert got.tolist() == [big.search(words[i]) for i in order]
        for row, i in enumerate(order):
            if rulelint.dfa_language_subset(dfas[i], big):
                assert got[row], (pats[i], "is inside a pattern that refuses its shortest match")


def test_row_ids_partition_rows_alike_on_both_paths():
    from coraza_kubernetes_operator_tpu.compiler.re_dfa import _row_ids

    rows = np.random.default_rng(37).integers(0, 3, (600, 4))
    small, large = _row_ids(rows[:500]), _row_ids(rows)[:500]
    assert len(set(small.tolist())) == len({r.tobytes() for r in rows[:500]})
    for a in range(0, 500, 7):  # the same rows share an id on either path
        same = (rows[:500] == rows[a]).all(axis=1)
        assert (small[same] == small[a]).all() and (small[~same] != small[a]).all()
        assert (large[same] == large[a]).all() and (large[~same] != large[a]).all()


# -- the configuration's files, JAX-free -----------------------------------------------------


def test_the_other_23_rule_files_are_crs_lite_pl2s():
    base = CONFIGS / "crs-lite-pl2" / "rules"
    mine = {p.relative_to(NEW / "rules"): p for p in (NEW / "rules").rglob("*") if p.is_file()}
    theirs = {p.relative_to(base): p for p in base.rglob("*") if p.is_file()}
    assert set(mine) - set(theirs) == {Path(freeze_custom.FEED_FILE)}
    assert len([p for p in theirs if p.suffix == ".conf"]) == 23
    for rel, p in theirs.items():
        assert mine[rel].read_bytes() == p.read_bytes(), rel
    spec = json.loads((NEW / "freeze.json").read_text())
    feed = freeze_custom.feed_rules(spec["feed_rules"], spec["feed_seed"])
    assert (NEW / "rules" / freeze_custom.FEED_FILE).read_text() == freeze_custom.feed_text(feed)
    # the feed is read before the CRS families, after crs-setup.conf
    text = harness.read_rules(NEW / "rules")
    assert text.index("id:900110") < text.index("id:9000000") < text.index("id:905100")


def test_the_cell_resolves_by_name_and_deploys_crs_lite_pl2s_argv():
    cell = harness.Cell("crs-custom5k.ftw-salted-c1")
    base = harness.Cell("crs-lite.ftw-salted-c1")
    assert cell.config["name"] == "crs-lite-pl2-custom5k" and cell.config_dir == NEW
    assert "instances" not in cell.config and "sidecar_args" not in cell.config
    assert cell.sidecar_argv(1, 2, "d") == base.sidecar_argv(1, 2, "d")
    assert cell.mix == dict(base.mix, plan="ftw-custom-salted")
    names = [m["name"] for m in cell.metrics("per_layer")]
    for new in ("matcher_device_ms_per_kilorule", "dense_blocks_outside_bins"):
        assert new in names
        assert new not in [m["name"] for m in base.metrics("per_layer")]
    for name in names:
        assert callable(cell.reader(name).read), name
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "verdicts_per_s", "latency_p50_ms", "latency_p95_ms", "setup_s"}
    control = cell.rules_text(control=True)
    assert control.rstrip().endswith("SecRuleRemoveById 9000000-9004999")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "crs-lite-pl2-custom5k")
    assert entry["reduced"] == cell.config["reduced"] and len(entry["source"]) <= 200
    # the contract's form of a name: 64 characters at most, each key of `reduced` too
    cell_entry = cell.workload
    for key in [entry["name"], cell_entry["name"], cell_entry["traffic"], *entry["reduced"]]:
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", key), key
    assert len(entry["why"]) <= 200 and len(cell_entry["why"]) <= 200


def test_the_plan_is_ftw_salteds_groups_with_one_custom_request_each():
    cell = harness.Cell("crs-custom5k.ftw-salted-c1")
    plan = json.loads((NEW / "plans" / "ftw-custom-salted.json").read_text())
    base = json.loads((CONFIGS / "crs-lite-pl2" / "plans" / "ftw-salted.json").read_text())
    pool = [json.loads(line) for line in open(NEW / "corpus.jsonl")]
    frozen = json.loads((NEW / "frozen.json").read_text())
    n_base = frozen["base_requests"]
    assert frozen["moved_by_feed"] == [] and n_base == 277 and len(pool) == 277 + 46
    assert frozen["rules_compiled"] == 5269 and frozen["rules_skipped"] == 0
    assert plan["tier_shapes"] == [[32, 512]]
    assert len(plan["steady"]) == len(base["steady"]) == 46
    seen = []
    for mine, theirs in zip(plan["steady"], base["steady"]):
        assert mine["lane"] == theirs["lane"] and mine["requests"][:-1] == theirs["requests"]
        assert len(mine["requests"]) == 7 and mine["requests"][-1] >= n_base
        assert mine["unique_uncached_rows"] <= 30 and mine["tier_shapes"] == [[32, 512]]
        seen.append(mine["requests"][-1])
    assert sorted(seen) == list(range(n_base, n_base + 46))  # each custom request once a pass
    for lane in ("interactive", "bulk"):  # blocked and near-miss alternate within a lane
        kinds = [pool[b["requests"][-1]]["status"] for b in plan["steady"] if b["lane"] == lane]
        assert kinds == [403, 200] * (len(kinds) // 2)
    for g in plan["prime"]:
        assert g["tier_shapes"] == [[32, 512]] and 18 <= g["unique_uncached_rows"] <= 30
    primed = [i for g in plan["prime"] for i in g["requests"]]
    assert set(range(n_base, n_base + 46)) <= set(primed)
    assert {i for g in base["prime"] for i in g["requests"]} <= set(primed)
    # 23 blocked, each by a rule of its own, the feed's ends among them
    blocked = [r for r in pool[n_base:] if r["status"] == 403]
    ids = sorted(int(r["rule_id"]) for r in blocked)
    assert len(ids) == len(set(ids)) == 23 == len([r for r in pool[n_base:] if r["status"] == 200])
    assert {9000000, 9000009, 9004999} <= set(ids) and ids == frozen["custom_blocked_by"]
    by = {tpl: sum(freeze_custom.TEMPLATE_OF[(i - 9000000) % 10] == tpl for i in ids)
          for tpl in TEMPLATES}
    assert min(by.values()) >= 4, by
    # a near-miss is its blocked request with one byte changed
    import base64

    for hit, near in zip(pool[n_base::2], pool[n_base + 1::2]):
        a, b = base64.b64decode(hit["wire"]), base64.b64decode(near["wire"])
        assert len(a) == len(b) and sum(x != y for x, y in zip(a, b)) == 1
    # the generator sends what the plan says: 46 bursts of 7 down one connection
    traffic = cell.traffic(2**31 + 37)
    assert [len(c) for c in traffic.connections] == [46]
    assert {b.n for b in traffic.connections[0]} == {7}
    assert len(traffic.prime) == len(plan["prime"])
