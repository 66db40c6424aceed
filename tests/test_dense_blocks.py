"""One family of dense-DFA blocks, one matcher for them (PR 48).

``build_model`` lists the dense blocks once (exact nfa buckets, dfa-hot
blocks, prefilter buckets), offers every one to the flat planner, and
stacks a ``DFABank`` only for a block the planner rejects. Pinned here:

- the layout did not move when the per-bank matchers were deleted:
  crs-lite's and the operator sample's column order, block kinds and
  costs, covered blocks, prefilter columns and bins equal what the parent
  commit built (``tests/data/layout_pins.json``; the custom feed's pin is
  in ``tests/test_custom_feed.py``), with the deleted switches set or not;
- a covered block holds no table of its own: no ``DFABank``, fewer leaves;
- the block OUTSIDE every bin, which no deployment has and no test built:
  one exact DFA past the planner's single-DFA limit with the prefilter
  off. It gets the one bank of the model and the plain gather scan, its
  columns equal ``DFA.search``, the engine's verdicts the host evaluator's;
- ``ops/dfa.py:scan_dfa_bank`` picks its formulation from the bank alone;
- the hot block splitter in its new home keeps its class cap.
"""

from __future__ import annotations

import json
import random
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import layout_pin

from coraza_kubernetes_operator_tpu.compiler import compile_regex_dfa, literal_dfa, pm_dfa
from coraza_kubernetes_operator_tpu.compiler.automata_plan import (
    _HOT_MAX_JOINT_CLASSES,
    cut_hot_blocks,
    plan_automata,
)
from coraza_kubernetes_operator_tpu.compiler.re_dfa import joint_class_count
from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules

HERE = Path(__file__).resolve().parent
PINS = json.loads((HERE / "data" / "layout_pins.json").read_text())
SAMPLE = (HERE.parent / "wafbench" / "configs" / "operator-sample" / "rules.conf").read_text()
# Leaves of crs-lite's model at the parent commit, where every covered
# block still carried its packed, classmap, match_end, always and dense
# table (or the joint-class twin of them) into every executable.
CRS_LITE_LEAVES_AT_PARENT = 228


def _engine(text_or_crs, env=()):
    from coraza_kubernetes_operator_tpu.engine import WafEngine

    with pytest.MonkeyPatch.context() as mp:
        for k in ("CKO_AUTOMATA", "CKO_PREFILTER", "CKO_NATIVE"):
            mp.delenv(k, raising=False)
        for k, v in env:
            mp.setenv(k, v)
        return WafEngine(text_or_crs)


@pytest.fixture(scope="module")
def crs_lite_rules():
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules_cached
    from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text

    return compile_rules_cached(load_ruleset_text(), str(HERE / ".crs_cache"))


@pytest.fixture(scope="module")
def crs_lite(crs_lite_rules):
    return _engine(crs_lite_rules)


# -- the layout pins ---------------------------------------------------------


def test_crs_lite_layout_is_the_parents(crs_lite):
    assert layout_pin(crs_lite.model) == PINS["crs-lite"]
    auto = crs_lite.automata_summary()
    assert (auto["flat_bins"], auto["flat_slots"], auto["flat_groups"]) == (2, 1408, 34)
    assert auto["per_bank_kernels"] == 0
    assert (auto["dfa_hot_blocks"], auto["prefilter_blocks"]) == (5, 5)


def test_operator_sample_layout_is_the_parents():
    eng = _engine(SAMPLE)
    assert layout_pin(eng.model) == PINS["operator-sample"]
    auto = eng.automata_summary()
    assert (auto["flat_bins"], auto["flat_slots"], auto["flat_groups"]) == (0, 0, 0)
    assert auto["per_bank_kernels"] == 0 and eng.model.dense_blocks == ()


def test_deleted_switches_are_not_read(crs_lite_rules):
    """``CKO_FLAT=0`` asked for the per-bank matchers and ``CKO_PALLAS=0``
    for their XLA twins: nothing reads either name any more."""
    eng = _engine(crs_lite_rules, env=(("CKO_FLAT", "0"), ("CKO_PALLAS", "0")))
    assert layout_pin(eng.model) == PINS["crs-lite"]
    assert eng.automata_summary()["per_bank_kernels"] == 0


def test_a_covered_block_holds_no_bank(crs_lite):
    m = crs_lite.model
    assert m.banks == [] and m.bank_blocks == ()
    n_segs = len(m.segs)
    assert m.flat_covered == tuple(range(n_segs, n_segs + len(m.dense_blocks)))
    assert [b.kind for b in m.dense_blocks] == ["nfa"] * 4 + ["dfa-hot"] * 5 + ["prefilter"] * 5
    leaves = len(jax.tree_util.tree_leaves(m))
    assert leaves < CRS_LITE_LEAVES_AT_PARENT and leaves == 163


def test_kind_and_states_of_a_block_stay_out_of_the_cache_key(crs_lite):
    """The executable cache keys on the treedef: a dense block's
    pipeline and group count shape the trace, its kind and state count
    do not (``DenseBlock``'s equality)."""
    import dataclasses

    from coraza_kubernetes_operator_tpu.models.waf_model import DenseBlock

    m = crs_lite.model
    treedef = jax.tree_util.tree_structure(m)
    other = tuple(DenseBlock(b.pipeline, b.groups, "nfa", b.states + 1) for b in m.dense_blocks)
    assert jax.tree_util.tree_structure(dataclasses.replace(m, dense_blocks=other)) == treedef
    fewer = tuple(dataclasses.replace(b, groups=b.groups + 1) for b in m.dense_blocks)
    assert jax.tree_util.tree_structure(dataclasses.replace(m, dense_blocks=fewer)) != treedef
    back = jax.tree_util.tree_unflatten(treedef, jax.tree_util.tree_leaves(m))
    assert [b.kind for b in back.dense_blocks] == [b.kind for b in m.dense_blocks]
    assert back.flat_covered == m.flat_covered and back.bank_blocks == ()


# -- the block outside every bin --------------------------------------------

# 7,168 exact states: past what one bin holds (about 3,200 states by
# ``flat_vmem_bytes`` under 15 MiB), no segment plan, and ``prefiltered``
# by default, so only a plan without the prefilter leaves it exact.
WIDE = "x(?:ab)*.{11}y"
OUTSIDE_RULES = f"""
SecRuleEngine On
SecDefaultAction "phase:2,log,deny,status:403"
SecRule ARGS|REQUEST_URI "@rx {WIDE}" "id:100,phase:2,deny,status:403,t:none"
SecRule ARGS|REQUEST_URI "@rx (e|fg)+h" "id:101,phase:2,deny,status:403,t:none"
SecRule ARGS|REQUEST_URI "@contains evilmonkey" "id:102,phase:2,deny,status:403,t:none"
"""


def _outside_rows(width: int, seed: int) -> list[bytes]:
    rng = random.Random(seed)
    rows = [b"", b"x" + b"ab" * 3 + b"0123456789a" + b"y", b"x" + b"." * 11 + b"y",
            b"x" + b"ab" * 2 + b"0123456789" + b"y", b"zzehzz", b"fgfgfg", b"evilmonkey"]
    rows += [b"x" + b"ab" * rng.randrange(0, 6) + bytes(rng.choices(b"abxy.", k=rng.randrange(9, 13)))
             + b"y" for _ in range(24)]
    rows += [bytes(rng.choices(b"abxyefgh.", k=rng.randrange(0, width + 1))) for _ in range(33)]
    return [r[:width] for r in rows]


@pytest.fixture(scope="module")
def outside_rules():
    return compile_rules(OUTSIDE_RULES)


def test_a_block_outside_every_bin_takes_the_bank_scan(outside_rules):
    from coraza_kubernetes_operator_tpu.models.waf_model import build_model, match_tier

    crs = outside_rules
    assert plan_automata(crs).counts()["prefiltered"] == 1
    plan = plan_automata(crs, prefilter_enabled=False)
    assert plan.counts() == {"segment": 1, "dfa-hot": 1, "prefiltered": 0, "nfa": 1}
    m = build_model(crs, plan)
    n_segs = len(m.segs)
    # per_bank_kernels is len(model.banks): the wide DFA's block alone
    assert len(m.banks) == 1 and m.bank_blocks == (n_segs,)
    assert m.banks[0].n_states == 7168 and m.banks[0].t256.size == 0  # no dense table
    assert m.flat_covered == (n_segs + 1,) and len(m.flat_banks) == 1
    assert [(b.kind, b.groups, b.states) for b in m.dense_blocks] == [("nfa", 1, 7168), ("dfa-hot", 1, 4)]
    assert m.block_cost[n_segs:] == (1000.0, 2.0)  # the gather scan; 0.5 * states * groups

    width = 32
    rows = _outside_rows(width, seed=48)
    data = np.zeros((len(rows), width), dtype=np.uint8)
    lengths = np.array([len(r) for r in rows], dtype=np.int32)
    for i, r in enumerate(rows):
        data[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
    none = jnp.zeros((1, len(rows), width), dtype=jnp.uint8), jnp.zeros((1, len(rows)), dtype=jnp.int32)
    hits = np.asarray(jax.jit(match_tier)(m, jnp.asarray(data), jnp.asarray(lengths), *none))
    want = np.array([[crs.groups[g].dfa.search(r) for g in m.group_order] for r in rows])
    np.testing.assert_array_equal(hits, want)
    wide_col = sum(s.n_groups for s in m.segs)  # the first dense block's one column
    assert crs.groups[m.group_order[wide_col]].dfa.n_states == 7168
    assert 4 <= want[:, wide_col].sum() < len(rows)
    # masked off, the block is zeros and its bank is not scanned
    masked = np.asarray(jax.jit(match_tier, static_argnames="mask")(
        m, jnp.asarray(data), jnp.asarray(lengths), *none, mask=~(1 << n_segs) & (2 ** (n_segs + 2) - 1)))
    assert not masked[:, wide_col].any()
    np.testing.assert_array_equal(np.delete(masked, wide_col, axis=1), np.delete(want, wide_col, axis=1))


def test_an_engine_with_a_block_outside_every_bin_answers_as_the_host_does(outside_rules):
    from coraza_kubernetes_operator_tpu.engine import HttpRequest

    eng = _engine(outside_rules, env=(("CKO_PREFILTER", "0"),))
    auto = eng.automata_summary()
    assert auto["per_bank_kernels"] == 1 and auto["flat_bins"] == 1
    assert auto["tiers"]["nfa"] == 1 and auto["prefilter_blocks"] == 0
    reqs = [HttpRequest(uri="/?q=" + r.decode()) for r in _outside_rows(40, seed=49) if b"\x00" not in r]
    got = eng.evaluate(reqs)
    host = eng.host_fallback.evaluate(reqs)
    key = lambda v: (v.status, v.interrupted, v.rule_id, tuple(v.matched_ids))  # noqa: E731
    assert [key(v) for v in got] == [key(v) for v in host]
    assert {v.rule_id for v in got if v.interrupted} == {100, 101, 102}


# -- ops/dfa.py: the plain bank scan -----------------------------------------

BANK = [
    compile_regex_dfa("^/admin"),
    compile_regex_dfa(r"(?i:<script[^>]*>)"),
    literal_dfa(b"evilmonkey"),
    compile_regex_dfa("passwd$"),
    compile_regex_dfa("a*"),  # always-match
    pm_dfa([b"sleep", b"benchmark", b"waitfor"]),
    compile_regex_dfa(r"\bor\b\s*['\"]?\d+['\"]?\s*=\s*['\"]?\d+"),
]


def _fuzz_rows(n, width, seed):
    rng = random.Random(seed)
    rows = [b"", b"/admin/panel", b"<script>alert(1)</script>", b"evilmonkey", b"/etc/passwd",
            b"or 1=1", b"benchmark(9)", b"a" * width]
    rows += [bytes(rng.choices(b"abcdefor1=' <>script/untilfwm", k=rng.randrange(0, width + 1)))
             for _ in range(n)]
    data = np.zeros((len(rows), width), dtype=np.uint8)
    for i, r in enumerate(rows):
        data[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
    return rows, jnp.asarray(data), jnp.asarray(np.array([len(r) for r in rows], dtype=np.int32))


def test_scan_dfa_bank_picks_its_scan_from_the_bank_alone(monkeypatch):
    """The take-scan where the bank has a dense table, the gather scan
    where it has none, whatever the backend says it is."""
    from coraza_kubernetes_operator_tpu.ops import dfa

    rows, data, lengths = _fuzz_rows(60, 48, seed=3)
    want = np.array([[d.search(r) for d in BANK] for r in rows])
    dense = dfa.stack_dfas(BANK)
    wide = dfa.stack_dfas(BANK, min_states=dfa._DENSE_MAX_STATES + 1)
    assert dense.t256.size and not wide.t256.size
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        calls = []
        for name in ("scan_dfa_bank_take", "scan_dfa_bank_gather"):
            real = getattr(dfa, name)
            monkeypatch.setattr(dfa, name, lambda *a, _r=real, _n=name: calls.append(_n) or _r(*a))
        np.testing.assert_array_equal(np.asarray(dfa.scan_dfa_bank(dense, data, lengths)), want)
        np.testing.assert_array_equal(np.asarray(dfa.scan_dfa_bank(wide, data, lengths)), want)
        assert calls == ["scan_dfa_bank_take", "scan_dfa_bank_gather"]
        monkeypatch.undo()


# -- the hot block splitter (compiler/automata_plan.py) ----------------------


def test_cut_hot_blocks_respects_class_cap():
    blocks = cut_hot_blocks(BANK)
    assert sorted(i for b in blocks for i in b) == list(range(len(BANK)))  # each DFA placed once
    assert blocks == sorted(blocks, key=lambda b: b[0]) and all(b == sorted(b) for b in blocks)
    for block in blocks:
        assert joint_class_count([BANK[i] for i in block]) <= _HOT_MAX_JOINT_CLASSES


@pytest.mark.slow
def test_crs_lite_hot_groups_match_oracle():
    """Sampled crs-lite hot-tier patterns: the plain bank scan over
    ``stack_dfas`` agrees with the scalar oracle on fuzzed traffic for
    the real CRS-shaped DFAs the planner routes to this tier."""
    from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text
    from coraza_kubernetes_operator_tpu.ops.dfa import scan_dfa_bank, stack_dfas

    crs = compile_rules(load_ruleset_text())
    plan = plan_automata(crs, enabled=True, hot_enabled=True)
    hot = [t for t in plan.tiers if t.kind == "dfa-hot"][:8]
    assert hot, "crs-lite must yield dfa-hot groups"
    dfas = [crs.groups[t.gid].dfa for t in hot]
    rows, data, lengths = _fuzz_rows(80, 80, seed=5)
    got = np.asarray(scan_dfa_bank(stack_dfas(dfas), data, lengths))
    for i, r in enumerate(rows):
        for g, dfa in enumerate(dfas):
            assert got[i, g] == dfa.search(r), (r, hot[g].gid)
