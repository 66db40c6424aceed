"""Two-level automata routing: verdict parity and the confirm path.

Builds the same compiled ruleset into two engines — automata on and
automata off — and proves:

- the plan routes groups to all three new tiers (segment stays segment,
  the small regex goes dfa-hot, the big one is prefiltered);
- verdicts are bit-identical between the two engines on benign traffic,
  exact hits, and approx-only (false-positive) traffic;
- prefilter positives reach the exact host confirm: hits >= confirms,
  false_positives == hits - confirms, and a crafted approx-only request
  increments false_positives WITHOUT changing the verdict.
"""

import os

import pytest

from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine

RULES = """
SecRuleEngine On
SecDefaultAction "phase:2,log,deny,status:403"
SecRule ARGS|REQUEST_URI "@rx (e|fg)+h" "id:100,phase:2,deny,status:403,t:none"
SecRule ARGS|REQUEST_URI "@rx (a|bc)*a(a|bc){7}d" "id:101,phase:2,deny,status:403,t:none"
SecRule ARGS|REQUEST_URI "@contains evilmonkey" "id:102,phase:2,deny,status:403,t:none"
"""

REQUESTS = [
    HttpRequest(uri="/index.html?q=hello"),  # benign
    HttpRequest(uri="/?q=xxaaaaaaaadxx"),  # exact hit for 101 (confirm upholds)
    HttpRequest(uri="/?q=bcbcbcbcd"),  # approx-only bait for 101
    HttpRequest(uri="/?q=zzehzz"),  # dfa-hot hit for 100
    HttpRequest(uri="/?q=evilmonkey"),  # segment hit for 102
    HttpRequest(uri="/?q=fgfgfgfg"),  # near-miss for 100 (no trailing h)
]


def _verdict_key(v):
    return (v.status, v.interrupted, v.rule_id, tuple(v.matched_ids))


@pytest.fixture(scope="module")
def engines():
    crs = compile_rules(RULES)
    saved = {k: os.environ.get(k) for k in ("CKO_AUTOMATA",)}
    try:
        os.environ["CKO_AUTOMATA"] = "0"
        off = WafEngine(crs)
        os.environ["CKO_AUTOMATA"] = "1"
        on = WafEngine(crs)
        yield on, off
    finally:
        for k, val in saved.items():
            if val is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = val


def test_plan_routes_all_tiers(engines):
    on, off = engines
    counts = on.automata_plan.counts()
    assert counts["dfa-hot"] >= 1
    assert counts["prefiltered"] >= 1
    assert counts["segment"] >= 1
    assert {b.kind for b in on.model.dense_blocks} == {"dfa-hot", "prefilter"}
    assert len(on.model.prefilter_cols) >= 1
    # The off engine keeps the exact pre-feature layout.
    assert off.automata_plan.counts()["dfa-hot"] == 0
    assert {b.kind for b in off.model.dense_blocks} == {"nfa"}
    assert not off.model.prefilter_cols


def test_verdict_parity_on_vs_off(engines):
    on, off = engines
    v_on = on.evaluate(REQUESTS)
    v_off = off.evaluate(REQUESTS)
    for a, b, r in zip(v_on, v_off, REQUESTS):
        assert _verdict_key(a) == _verdict_key(b), r.uri
    # Sanity on the expected outcomes (not just mutual agreement).
    assert v_on[0].allowed
    assert v_on[1].rule_id == 101
    assert v_on[2].allowed  # approx-only bait must NOT block
    assert v_on[3].rule_id == 100
    assert v_on[4].rule_id == 102
    assert v_on[5].allowed


def test_prefilter_positives_reach_exact_confirm(engines):
    on, _off = engines
    stats = dict(on.prefilter_stats)
    assert stats["hits"] >= 1  # the exact hit (and likely the bait) fired
    assert stats["confirms"] >= 1  # the exact hit was upheld
    assert stats["hits"] >= stats["confirms"]
    assert stats["false_positives"] == stats["hits"] - stats["confirms"]
    # The approx-only bait row must have been cleared by the confirm.
    assert stats["false_positives"] >= 1


def test_automata_summary_shape(engines):
    on, _off = engines
    summary = on.automata_summary()
    assert summary["enabled"] is True
    assert set(summary["tiers"]) == {"segment", "dfa-hot", "prefiltered", "nfa"}
    assert summary["dfa_hot_blocks"] >= 1
    assert summary["prefilter_blocks"] >= 1
    # Where the dense-DFA blocks are scanned: all of them in flat bins.
    assert summary["flat_bins"] >= 1 and summary["per_bank_kernels"] == 0
    assert summary["flat_groups"] == 2 and summary["flat_slots"] % 128 == 0
    assert {"rows", "hits", "confirms", "false_positives"} <= set(
        summary["prefilter"]
    )
