"""Sharded evaluation vs single-device reference on the virtual CPU mesh."""

import jax
import pytest

from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
from coraza_kubernetes_operator_tpu.parallel import ShardedWafEngine, make_mesh

RULES = r"""
SecRuleEngine On
SecRequestBodyAccess On
SecDefaultAction "phase:2,log,auditlog,deny,status:403"
SecRule ARGS "@rx (?i:(\b(select|union|insert|update|delete|drop)\b.*\b(from|into|where|table)\b))" \
  "id:942100,phase:2,deny,status:403,t:none,t:urlDecodeUni,msg:'SQLi'"
SecRule ARGS "@rx (?i:<script[^>]*>)" \
  "id:941100,phase:2,deny,status:403,t:none,t:urlDecodeUni,t:htmlEntityDecode,msg:'XSS'"
SecRule ARGS|REQUEST_URI|REQUEST_HEADERS "@contains evilmonkey" \
  "id:3001,phase:2,deny,status:403,t:none,t:urlDecodeUni,msg:'Monkey'"
SecRule ARGS "@pm sleep benchmark waitfor" "id:44,phase:2,deny,status:403,t:none,t:lowercase"
SecRule REQUEST_URI "@beginsWith /blocked" "id:45,phase:1,deny,status:403,t:none"
"""

REQUESTS = [
    HttpRequest(uri="/ok?q=hello"),
    HttpRequest(uri="/?q=union+select+a+from+b"),
    HttpRequest(uri="/?x=%3Cscript%3E"),
    HttpRequest(uri="/", headers=[("UA", "evilmonkey")]),
    HttpRequest(uri="/?q=SLEEP(9)"),
    HttpRequest(uri="/blocked/path"),
    HttpRequest(uri="/fine/path?a=1&b=2"),
    HttpRequest(
        method="POST",
        uri="/api",
        headers=[("Content-Type", "application/json")],
        body=b'{"q": "drop table x; select 1 from t"}',
    ),
    HttpRequest(uri="/also-ok"),
    HttpRequest(uri="/?deep=%26lt%3Bscript%26gt%3B"),
]


@pytest.mark.parametrize(
    "shape",
    [
        (2, 1),
        # Full mesh matrix is nightly-tier: each shape costs ~100 s on the
        # 8-device virtual CPU mesh (the driver's dryrun covers 4x2 too).
        pytest.param((4, 2), marks=pytest.mark.slow),
        pytest.param((2, 4), marks=pytest.mark.slow),
    ],
)
@pytest.mark.parametrize("class_gaps", ["latch", "matmul"])
def test_sharded_matches_single(shape, class_gaps, monkeypatch, request):
    n_data, n_rule = shape
    if len(jax.devices()) < n_data * n_rule:
        pytest.skip("not enough devices")
    if class_gaps == "matmul":
        if shape != (2, 1):
            pytest.skip("the matmul form's varying axes are the same on every mesh")
        # `<script[^>]*>` under shard_map with its gap as reachability matmuls
        # (ops/segment.py; no structure of these rules is large enough by itself)
        from coraza_kubernetes_operator_tpu.ops import segment

        monkeypatch.setattr(segment, "_REACH_MIN_ELEMS", 1)
        jax.clear_caches()  # match_segment_block's traces do not see the constant
        request.addfinalizer(jax.clear_caches)
    compiled = compile_rules(RULES)
    single = WafEngine(compiled)
    expected = single.evaluate(REQUESTS)

    mesh = make_mesh(n_data, n_rule)
    sharded = ShardedWafEngine(compiled=compiled, mesh=mesh)
    got = sharded.evaluate(REQUESTS)

    for i, (e, g) in enumerate(zip(expected, got)):
        assert g.interrupted == e.interrupted, (i, REQUESTS[i].uri)
        assert g.status == e.status, (i, REQUESTS[i].uri)
        assert g.rule_id == e.rule_id, (i, REQUESTS[i].uri)


def test_sharded_packed_taps_give_the_plain_convs_verdicts(monkeypatch, request):
    """The rule-sharded path traces the conv tier under ``shard_map`` with
    ``128 // C`` taps a contraction (``ops/segment.py:conv_tap_packing``):
    its verdicts are the same path's with one tap a contraction (the
    packing patched to 1), and both hold the attacks."""
    from coraza_kubernetes_operator_tpu.ops import segment

    if len(jax.devices()) < 2:
        pytest.skip("not enough devices")
    sharded = ShardedWafEngine(compiled=compile_rules(RULES), mesh=make_mesh(2, 1))
    assert sharded.model.segs and all(segment.conv_tap_packing(b.spec)[0] > 1 for b in sharded.model.segs)
    packed = sharded.evaluate(REQUESTS)

    monkeypatch.setattr(segment, "conv_tap_packing", lambda spec: (1, spec.w))
    jax.clear_caches()  # match_segment_block's traces do not see the patch
    request.addfinalizer(jax.clear_caches)
    plain = sharded.evaluate(REQUESTS)

    assert [(v.interrupted, v.status, v.rule_id) for v in packed] == [
        (v.interrupted, v.status, v.rule_id) for v in plain]
    assert [v.rule_id for v in packed[1:6]] == [942100, 941100, 3001, 44, 45]


def test_mesh_device_requirements():
    with pytest.raises(ValueError):
        make_mesh(1000, 1000)


def test_sharded_long_body_fallback(monkeypatch):
    """The rule-sharded path must take the same constant-memory DFA
    fallback for long shape buckets as the single-chip path (the conv
    bitmap is per-device, so the budget applies per shard)."""
    import jax as _jax

    from coraza_kubernetes_operator_tpu.models import waf_model

    if len(jax.devices()) < 2:
        pytest.skip("not enough devices")
    rules = (
        "SecRuleEngine On\nSecRequestBodyAccess On\n"
        'SecRule ARGS "@rx (?i:\\bunion\\s+select\\b)" "id:1,phase:2,deny,status:403,t:none,t:urlDecodeUni"\n'
        'SecRule ARGS "@contains evilmonkey" "id:2,phase:2,deny,status:403,t:none"\n'
    )
    filler = "z" * 400
    reqs = [
        HttpRequest(uri=f"/?q={filler}+union+select+a+from+b"),
        HttpRequest(uri=f"/?q={filler}+benign"),
        HttpRequest(uri=f"/?q={filler}+evilmonkey"),
        HttpRequest(uri="/short"),
    ]
    compiled = compile_rules(rules)
    single = WafEngine(compiled)
    expected = single.evaluate(reqs)

    monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", 1)  # force long tier
    _jax.clear_caches()
    try:
        sharded = ShardedWafEngine(compiled=compiled, mesh=make_mesh(2, 1))
        got = sharded.evaluate(reqs)
        for i, (e, g) in enumerate(zip(expected, got)):
            assert g.interrupted == e.interrupted, i
            assert g.status == e.status, i
            assert g.rule_id == e.rule_id, i
    finally:
        _jax.clear_caches()  # drop long-tier executables traced under the tiny budget
