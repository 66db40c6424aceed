"""Kind-partitioned matching: differential equivalence vs the unmasked path.

ADVICE r4 (medium): partitions only form when a length tier splits into
>= 2 partitions of >= _MIN_PART_ROWS rows, which no test reached — the
block-skip / zeros / column-reassembly plumbing shipped unverified. Here
_MIN_PART_ROWS is forced to 1 so mixed header/args/body traffic fans out
into real multi-partition tiers, and the partitioned verdicts (and
matched_ids, scores) must equal the masks=None full-scan path's exactly,
including with the chunked-conv branch active.
"""

import numpy as np
import pytest

import coraza_kubernetes_operator_tpu.engine.waf as waf_mod
import coraza_kubernetes_operator_tpu.models.waf_model as model_mod
from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine

# Rules spread across kinds so kind classes differ: header-only rules,
# arg-only rules, URI rules, body rules — plus an anomaly-threshold pair.
RULES = r"""
SecRuleEngine On
SecRequestBodyAccess On
SecDefaultAction "phase:2,log,pass"
SecAction "id:900100,phase:1,nolog,pass,setvar:tx.score=0"
SecRule REQUEST_HEADERS:User-Agent "@contains sqlmap" \
  "id:6001,phase:1,deny,status:403,t:lowercase"
SecRule REQUEST_HEADERS "@rx (?i)x-attack-[a-z]+" "id:6002,phase:1,pass,setvar:tx.score=+5"
SecRule ARGS "@rx (?i)union\s+select" "id:6003,phase:2,pass,setvar:tx.score=+5"
SecRule ARGS|REQUEST_URI "@contains ../" "id:6004,phase:2,deny,status:403"
SecRule REQUEST_URI "@beginsWith /admin" "id:6005,phase:1,pass,setvar:tx.score=+3"
SecRule REQUEST_BODY "@rx <script[^>]*>" "id:6006,phase:2,deny,status:403,t:lowercase"
SecRule REQUEST_COOKIES "@contains evilcookie" "id:6007,phase:2,deny,status:403"
SecRule TX:score "@ge 8" "id:6999,phase:2,deny,status:406"
"""


def _traffic(n=96):
    reqs = []
    for i in range(n):
        kind = i % 8
        if kind == 0:
            reqs.append(
                HttpRequest(
                    method="GET",
                    uri=f"/shop/item{i}?q=v{i}",
                    headers=[("Host", "a.example"), ("User-Agent", "curl/8.0")],
                )
            )
        elif kind == 1:
            reqs.append(
                HttpRequest(
                    method="GET",
                    uri=f"/search?q=1+UNION+SELECT+password{i}",
                    headers=[("Host", "b.example"), ("User-Agent", "sqlmap/1.7")],
                )
            )
        elif kind == 2:
            reqs.append(
                HttpRequest(
                    method="GET",
                    uri=f"/admin/panel{i}",
                    headers=[("X-Probe", "x-attack-now"), ("User-Agent", "Mozilla")],
                )
            )
        elif kind == 3:
            reqs.append(
                HttpRequest(
                    method="POST",
                    uri=f"/upload{i}",
                    headers=[("Content-Type", "text/plain")],
                    body=b"hello <SCRIPT src=x> world " + bytes([65 + i % 26]) * (i % 300),
                )
            )
        elif kind == 4:
            reqs.append(
                HttpRequest(
                    method="GET",
                    uri=f"/files?path=../../etc/passwd{i}",
                    headers=[("Cookie", f"session=s{i}; theme=dark")],
                )
            )
        elif kind == 5:
            reqs.append(
                HttpRequest(
                    method="GET",
                    uri=f"/ok{i}",
                    headers=[("Cookie", "c=evilcookie")],
                )
            )
        else:
            reqs.append(
                HttpRequest(
                    method="POST",
                    uri=f"/form{i}",
                    headers=[("User-Agent", f"agent-{i}")],
                    body=b"field=value&x=" + bytes([97 + i % 26]) * (i % 600),
                )
            )
    return reqs


def _verdict_tuples(engine, tiers, numvals, n, masks):
    vs = engine._verdicts_from_tiers(tiers, numvals, n, masks=masks)
    return [
        (v.interrupted, v.status, v.rule_id, tuple(v.matched_ids), tuple(sorted(v.scores.items())))
        for v in vs
    ]


def _tensorize(engine, reqs):
    if engine.native_enabled:
        return engine._native.tensorize(reqs)
    return engine._tensorize([engine.extractor.extract(r) for r in reqs])


@pytest.mark.parametrize("chunked_conv", [False, True])
def test_partitioned_equals_unmasked(monkeypatch, chunked_conv):
    monkeypatch.setattr(waf_mod, "_MIN_PART_ROWS", 1)
    monkeypatch.setattr(waf_mod, "_MIN_TIER_ROWS", 8)
    if chunked_conv:
        # Force the lax.map row-chunked conv branch inside partitions.
        monkeypatch.setattr(model_mod, "_SEG_CHUNK_ELEMS", 1 << 14)
    engine = WafEngine(RULES)
    reqs = _traffic()
    tensors = _tensorize(engine, reqs)

    tiers_p, nv_p, masks_p = waf_mod.tier_tensors(tensors, engine._kind_block_lut)
    tiers_f, nv_f, masks_f = waf_mod.tier_tensors(tensors, None)

    # The point of the test: real multi-partition tiers with real masks.
    n_masked = sum(1 for m in masks_p if m is not None)
    assert n_masked >= 2, f"partitions never formed: masks={masks_p}"
    assert len(tiers_p) > len(tiers_f)
    assert all(m is None for m in masks_f)

    got = _verdict_tuples(engine, tiers_p, nv_p, len(reqs), masks_p)
    want = _verdict_tuples(engine, tiers_f, nv_f, len(reqs), masks_f)
    assert got == want

    # Sanity: the traffic actually exercises blocking + anomaly rules.
    interrupted = [g for g in got if g[0]]
    assert len(interrupted) >= 24
    assert any(g[2] == 6999 for g in got)  # anomaly threshold fired


def test_partition_masks_skip_blocks(monkeypatch):
    """Masks are real subsets: at least one partition's mask excludes at
    least one matcher block (otherwise partitioning is a no-op)."""
    monkeypatch.setattr(waf_mod, "_MIN_PART_ROWS", 1)
    monkeypatch.setattr(waf_mod, "_MIN_TIER_ROWS", 8)
    engine = WafEngine(RULES)
    n_blocks = len(engine.model.block_kinds)
    full = (1 << min(n_blocks, 62)) - 1
    _tiers, _nv, masks = waf_mod.tier_tensors(
        _tensorize(engine, _traffic()), engine._kind_block_lut
    )
    partial = [m for m in masks if m is not None and (m & full) != full]
    assert partial, f"no mask ever excluded a block: {masks}"


def test_short_masks_tuple_rejected(monkeypatch):
    """A masks tuple shorter than tiers is refused where the window's
    launch is resolved (``WafEngine._tier_specs``) instead of zipping
    the trailing tiers away, so the served dispatch cannot miss their
    matches (ADVICE r4 low)."""
    monkeypatch.setattr(waf_mod, "_MIN_PART_ROWS", 1)
    monkeypatch.setattr(waf_mod, "_MIN_TIER_ROWS", 8)
    engine = WafEngine(RULES)
    reqs = _traffic()
    tiers, numvals, masks = waf_mod.tier_tensors(
        _tensorize(engine, reqs), engine._kind_block_lut
    )
    assert len(tiers) >= 2, f"the window never tiered: {len(tiers)}"
    with pytest.raises(ValueError, match="masks length"):
        engine._tier_specs(tiers, numvals, masks=masks[:-1])
    with pytest.raises(ValueError, match="masks length"):
        engine._verdicts_from_tiers(tiers, numvals, len(reqs), masks=masks[:-1])
