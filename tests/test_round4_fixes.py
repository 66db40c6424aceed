"""Round-4 semantic fixes, unit-pinned.

Covers the engine changes behind the conformance reconciliation and the
advisor findings: SecRequestBodyLimitAction Reject (413), order-aware
ctl:ruleRemoveById chains, the tightened multipart boundary-candidate
heuristic (both host paths), and the strict native bulk-JSON grammar
(reference parity targets: Coraza body-limit interruption and in-order
ctl semantics; CRS 922120's MULTIPART_UNMATCHED_BOUNDARY).
"""

import json

import pytest

from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine

from conftest import native_engine

BASE = """
SecRuleEngine On
SecRequestBodyAccess On
SecDefaultAction "phase:2,log,auditlog,pass"
"""


def _post(body: bytes, ctype: str = "application/octet-stream", uri: str = "/up"):
    return HttpRequest(
        method="POST",
        uri=uri,
        headers=[("Host", "t.local"), ("Content-Type", ctype)],
        body=body,
    )


# -- SecRequestBodyLimitAction ------------------------------------------------


LIMIT_RULES = (
    BASE
    + "SecRequestBodyLimit 4096\n"
    + "SecRequestBodyLimitAction Reject\n"
    + 'SecRule REQUEST_BODY "@contains evilword" "id:10,phase:2,deny,status:403,t:none"\n'
)


@pytest.fixture(scope="module")
def limit_engine():
    return WafEngine(LIMIT_RULES)


@pytest.fixture(scope="module")
def native_limit_engine(native_lib):
    """The same rules on the native tensorizer (conftest.py builds the
    library); the other tests of this file keep the Python one."""
    eng = native_engine(LIMIT_RULES, native_lib)
    assert eng.native_enabled
    return eng


def test_body_over_limit_rejected_413(limit_engine):
    v = limit_engine.evaluate_one(_post(b"z" * 5000))
    assert v.interrupted and v.status == 413
    assert v.matched_ids == []


def test_body_at_limit_evaluated(limit_engine):
    v = limit_engine.evaluate_one(_post(b"z" * 4000 + b" evilword"))
    assert v.interrupted and v.status == 403


def test_body_over_limit_mixed_batch(limit_engine):
    reqs = [
        _post(b"ok"),
        _post(b"z" * 5000),
        _post(b"evilword"),
    ]
    vs = limit_engine.evaluate(reqs)
    assert [(v.interrupted, v.status) for v in vs] == [
        (False, 200),
        (True, 413),
        (True, 403),
    ]


def test_process_partial_truncates_instead():
    eng = WafEngine(
        BASE
        + "SecRequestBodyLimit 64\n"
        + "SecRequestBodyLimitAction ProcessPartial\n"
        + 'SecRule REQUEST_BODY "@contains evilword" "id:10,phase:2,deny,status:403,t:none"\n'
    )
    # Payload beyond the limit is truncated away: request passes.
    v = eng.evaluate_one(_post(b"a" * 64 + b"evilword"))
    assert not v.interrupted
    # Payload within the prefix still caught.
    v = eng.evaluate_one(_post(b"evilword" + b"a" * 100))
    assert v.interrupted and v.status == 403


def test_bulk_fast_path_rejects_over_limit(native_limit_engine):
    payload = json.dumps(
        {
            "requests": [
                {"method": "POST", "uri": "/up", "headers": [["Content-Type", "application/octet-stream"]], "body": "ok"},
                {"method": "POST", "uri": "/up", "headers": [["Content-Type", "application/octet-stream"]], "body": "z" * 5000},
                {"method": "POST", "uri": "/up", "headers": [["Content-Type", "application/octet-stream"]], "body": "evilword"},
            ]
        }
    ).encode()
    out = native_limit_engine.evaluate_bulk_json(payload)
    assert out is not None
    verdicts, _blob = out
    assert [(v.interrupted, v.status) for v in verdicts] == [
        (False, 200),
        (True, 413),
        (True, 403),
    ]


# -- order-aware ctl removal chains ------------------------------------------


CTL_CHAIN = (
    BASE
    + 'SecRule ARGS:t1 "@streq yes" "id:9001,phase:2,pass,t:none,nolog,ctl:ruleRemoveById=9002"\n'
    + 'SecRule ARGS:t2 "@streq yes" "id:9002,phase:2,pass,t:none,nolog,ctl:ruleRemoveById=9003"\n'
    + 'SecRule ARGS:attack "@contains evil" "id:9003,phase:2,deny,status:403,t:none"\n'
)


@pytest.fixture(scope="module")
def ctl_engine():
    return WafEngine(CTL_CHAIN)


def _get(uri):
    return HttpRequest(method="GET", uri=uri, headers=[("Host", "t.local")])


def test_ctl_removal_applies(ctl_engine):
    # 9002 fires alone: 9003 removed, attack passes.
    v = ctl_engine.evaluate_one(_get("/?t2=yes&attack=evil"))
    assert not v.interrupted
    assert 9003 not in v.matched_ids


def test_ctl_removal_chain_in_order(ctl_engine):
    # 9001 removes 9002 BEFORE 9002 applies its own removal, so 9003
    # stays live and blocks (a removed ctl rule never fires — Coraza
    # in-order semantics; the round-3 single-pass matrix got this wrong).
    v = ctl_engine.evaluate_one(_get("/?t1=yes&t2=yes&attack=evil"))
    assert v.interrupted and v.status == 403
    assert 9003 in v.matched_ids


def test_ctl_untriggered_keeps_rule(ctl_engine):
    v = ctl_engine.evaluate_one(_get("/?attack=evil"))
    assert v.interrupted and v.status == 403


# -- multipart boundary-candidate heuristic ----------------------------------


MP_RULES = (
    BASE
    + 'SecRule MULTIPART_UNMATCHED_BOUNDARY "@eq 1" "id:22,phase:2,deny,status:403,t:none"\n'
)


def _mp(body: bytes):
    return HttpRequest(
        method="POST",
        uri="/up",
        headers=[
            ("Host", "t.local"),
            ("Content-Type", "multipart/form-data; boundary=XB"),
        ],
        body=body,
    )


@pytest.fixture(scope="module")
def mp_engine():
    return WafEngine(MP_RULES)


@pytest.fixture(scope="module")
def native_mp_engine(native_lib):
    eng = native_engine(MP_RULES, native_lib)
    assert eng.native_enabled
    return eng


def _part(content: bytes) -> bytes:
    return (
        b'--XB\r\nContent-Disposition: form-data; name="a"\r\n\r\n'
        + content
        + b"\r\n--XB--\r\n"
    )


def test_pem_block_not_flagged(mp_engine):
    v = mp_engine.evaluate_one(
        _mp(_part(b"-----BEGIN CERTIFICATE-----\nMIIB\n-----END CERTIFICATE-----"))
    )
    assert not v.interrupted


def test_markdown_rule_not_flagged(mp_engine):
    v = mp_engine.evaluate_one(_mp(_part(b"para one\n-----\npara two")))
    assert not v.interrupted


def test_prose_dashes_with_space_not_flagged(mp_engine):
    v = mp_engine.evaluate_one(_mp(_part(b"-- see the flag list below")))
    assert not v.interrupted


def test_smuggled_boundary_still_flagged(mp_engine):
    v = mp_engine.evaluate_one(_mp(_part(b"--SMUGGLED")))
    assert v.interrupted and v.status == 403


def test_boundary_heuristic_native_parity(native_mp_engine):
    bodies = [
        _part(b"-----BEGIN CERTIFICATE-----"),
        _part(b"-----"),
        _part(b"--verbose"),
        _part(b"--SMUGGLED"),
        _part(b"-- spaced out"),
    ]
    reqs = [_mp(b) for b in bodies]
    native = [v.interrupted for v in native_mp_engine.evaluate(reqs)]

    saved = native_mp_engine._native

    class _Off:
        available = False

    native_mp_engine._native = _Off()
    try:
        python = [v.interrupted for v in native_mp_engine.evaluate(reqs)]
    finally:
        native_mp_engine._native = saved
    assert native == python, (native, python)


# -- strict native bulk JSON --------------------------------------------------


STRICT_CASES = [
    # missing comma between members
    b'{"requests": [{"method": "GET" "uri": "/"}]}',
    # garbage primitive value
    b'{"requests": [{"method": "GET", "uri": "/", "x": nonsense}]}',
    # trailing garbage after the object
    b'{"requests": []} trailing',
    # trailing comma in object
    b'{"requests": [{"method": "GET",}]}',
    # unterminated top-level object
    b'{"requests": []',
]


def test_native_json_strict_rejects(native_limit_engine):
    for payload in STRICT_CASES:
        assert native_limit_engine.evaluate_bulk_json(payload) is None, payload


def test_native_json_still_accepts_valid(native_limit_engine):
    payload = json.dumps(
        {
            "requests": [
                {
                    "method": "GET",
                    "uri": "/ok",
                    "version": "HTTP/1.1",
                    "headers": [["Host", "t.local"], ["Accept", "*/*"]],
                    "body": "",
                    "remote_addr": "10.0.0.1",
                    "tenant": None,
                }
            ]
        }
    ).encode()
    out = native_limit_engine.evaluate_bulk_json(payload)
    assert out is not None
    verdicts, _ = out
    assert len(verdicts) == 1 and not verdicts[0].interrupted


# -- row-chunked conv tier ----------------------------------------------------


def test_seg_row_chunking_matches_direct(monkeypatch):
    """A tier whose bitmap exceeds the per-chunk budget runs the SAME
    conv matchers in lax.map row chunks — verdicts and matched sets must
    be identical to the direct path (waf_model.segment_tier_hits)."""
    import jax

    from coraza_kubernetes_operator_tpu.models import waf_model

    rules = BASE + (
        'SecRule ARGS "@rx (?i:\\bunion\\s+select\\b)" "id:1,phase:2,deny,status:403,t:none,t:urlDecodeUni"\n'
        'SecRule ARGS "@contains evilmonkey" "id:2,phase:2,deny,status:403,t:none"\n'
        'SecRule REQUEST_HEADERS:User-Agent "@pm sqlmap nikto" "id:3,phase:1,deny,status:403,t:none,t:lowercase"\n'
    )
    eng = WafEngine(rules)
    reqs = []
    for i in range(8):
        reqs += [
            HttpRequest(uri=f"/?q=union+select+a{i}"),
            HttpRequest(uri=f"/?q=benign+value+{i}"),
            HttpRequest(uri=f"/?note=evilmonkey{i}"),
            HttpRequest(uri=f"/{i}", headers=[("User-Agent", "sqlmap/1.0")]),
        ]

    direct = eng.evaluate(reqs)
    # Per-chunk budget small enough that the ~100-row tier needs several
    # chunks, but >= 8 rows/chunk (for any tier width up to 64) so the
    # chunked path — not the long-bank fallback — is selected.
    from coraza_kubernetes_operator_tpu.ops.segment import conv_n2_cols

    n2 = sum(conv_n2_cols(s.spec) for s in eng.model.segs)
    assert n2 > 0
    monkeypatch.setattr(waf_model, "_SEG_CHUNK_ELEMS", 16 * 66 * n2)
    jax.clear_caches()
    try:
        chunked = eng.evaluate(reqs)
    finally:
        jax.clear_caches()

    for j, (d, c) in enumerate(zip(direct, chunked)):
        assert d.interrupted == c.interrupted, j
        assert d.status == c.status, j
        assert d.rule_id == c.rule_id, j
        assert d.matched_ids == c.matched_ids, j
    assert direct[0].interrupted and direct[0].rule_id == 1
    assert direct[1].allowed
    assert direct[2].interrupted and direct[2].rule_id == 2
    assert direct[3].interrupted and direct[3].rule_id == 3
