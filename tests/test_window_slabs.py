"""The launch side of the window slabs (ISSUE 36; models/slab.py).

A window reaches the device as one match slab a tier and one post slab
(``native/arena.py``); ``tests/test_native_tiered.py`` pins where the
host's views lie. Here: the two slab-taking executables against the
host twins, bit for bit, over every combination that changes the
layout; that what ``prewarm`` compiles is what a window launches; and
the count of host operands a window hands over
(``tiering.host_operands``).
"""

import numpy as np
import pytest

import coraza_kubernetes_operator_tpu.engine.waf as waf_mod
from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE
from coraza_kubernetes_operator_tpu.models.slab import post_layout
from coraza_kubernetes_operator_tpu.models.waf_model import (
    eval_post_tiered,
    match_tier_packed,
)
from coraza_kubernetes_operator_tpu.native import serialize_requests
from coraza_kubernetes_operator_tpu.native.arena import stage_window

pytestmark = pytest.mark.usefixtures("native_loaded")

# Kinds that differ by rule (headers, args, URI, body, cookies), so that
# kind partitions form; one, or two, pipelines the host has to apply
# (``cmdLine`` / ``normalizePath`` are no device transforms): H = 1, 2.
_BASE = r"""
SecRuleEngine On
SecRequestBodyAccess On
SecDefaultAction "phase:2,log,pass"
SecAction "id:900100,phase:1,nolog,pass,setvar:tx.score=0"
SecRule REQUEST_HEADERS:User-Agent "@contains sqlmap" "id:7001,phase:1,deny,status:403,t:lowercase"
SecRule ARGS "@rx (?i)union\s+select" "id:7003,phase:2,pass,setvar:tx.score=+5"
SecRule ARGS|REQUEST_URI "@contains ../" "id:7004,phase:2,deny,status:403"
SecRule REQUEST_URI "@beginsWith /admin" "id:7005,phase:1,pass,setvar:tx.score=+3"
SecRule REQUEST_BODY "@rx <script[^>]*>" "id:7006,phase:2,deny,status:403,t:lowercase"
SecRule REQUEST_COOKIES "@contains evilcookie" "id:7007,phase:2,deny,status:403"
SecRule ARGS "@contains cat /etc/passwd" "id:7008,phase:2,deny,status:403,t:cmdLine"
SecRule TX:score "@ge 8" "id:7999,phase:2,deny,status:406"
"""
_RULES = {
    1: _BASE,
    2: _BASE
    + 'SecRule REQUEST_URI "@contains /etc/shadow" "id:7009,phase:2,deny,status:403,t:normalizePath"\n',
}


def _traffic(n: int, salt: str):
    reqs = []
    for i in range(n):
        kind = i % 6
        if kind == 0:
            reqs.append(HttpRequest(
                uri=f"/shop/{salt}{i}?q=v{i}",
                headers=[("Host", "a.example"), ("User-Agent", "curl/8.0")]))
        elif kind == 1:
            reqs.append(HttpRequest(
                uri=f"/search?q=1+UNION+SELECT+{salt}{i}",
                headers=[("User-Agent", "sqlmap/1.7")]))
        elif kind == 2:
            reqs.append(HttpRequest(
                uri=f"/admin/{salt}{i}?c=c^at+/etc/pass\"wd",
                headers=[("Cookie", f"s={salt}{i}; c=evilcookie")]))
        elif kind == 3:
            reqs.append(HttpRequest(
                method="POST", uri=f"/upload/{salt}{i}",
                headers=[("Content-Type", "text/plain")],
                body=b"hello <SCRIPT src=x> " + salt.encode() + bytes([65 + i % 26]) * (i % 300)))
        elif kind == 4:
            reqs.append(HttpRequest(uri=f"/a/./b/../../etc/shadow?{salt}={i}"))
        else:
            reqs.append(HttpRequest(
                method="POST", uri=f"/form/{salt}{i}",
                headers=[("User-Agent", f"agent-{i}")],
                body=b"field=value&x=" + bytes([97 + i % 26]) * (90 + i % 500)))
    return reqs


_ENGINES: dict = {}


def _engine(h: int, cache_on: bool) -> WafEngine:
    """One engine a (host pipelines, value cache) pair for the module."""
    key = (h, cache_on)
    if key not in _ENGINES:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("CKO_VALUE_CACHE_MB", "256" if cache_on else "0")
            eng = WafEngine(_RULES[h])
        assert (eng.value_cache is not None) == cache_on
        assert len(eng._host_pipelines) == h
        _ENGINES[key] = eng
    return _ENGINES[key]


# -- (b) the slab-taking executables against the host twins ---------------------


@pytest.mark.parametrize("masked", [False, True], ids=["mask-none", "mask-set"])
@pytest.mark.parametrize("cache_on", [False, True], ids=["cache-off", "cache-on"])
@pytest.mark.parametrize("h", [1, 2], ids=["h1", "h2"])
@pytest.mark.parametrize("n_tiers", [1, 2], ids=["one-tier", "two-tier"])
def test_slab_executables_equal_host_twins(monkeypatch, n_tiers, h, cache_on, masked):
    """``match_tier_packed`` on a tier's match slab and
    ``eval_post_tiered`` on the window's post slab give, bit for bit,
    what ``_host_tier_hits`` and ``_host_post`` compute from the nine
    arrays a tier, ``numvals`` and the cached rows."""
    monkeypatch.setattr(waf_mod, "_MIN_TIER_ROWS", 8 if n_tiers == 2 else 1 << 20)
    monkeypatch.setattr(waf_mod, "_MIN_PART_ROWS", 1 if masked else 1 << 20)
    eng = _engine(h, cache_on)
    tag = f"{n_tiers}{h}{int(cache_on)}{int(masked)}"

    def tiered(reqs):
        return eng.tier_cached(eng._tensorize([eng.extractor.extract(r) for r in reqs]))

    if cache_on:
        # Fill the value cache under the same tiering, then send half
        # the window again: its post slab carries real cached rows.
        seen = _traffic(48, f"old{tag}")
        tiers, numvals, masks, cached, keys = tiered(seen)
        eng._verdicts_from_tiers(
            tiers, numvals, len(seen), masks=masks, cached=cached, miss_keys=keys)
        reqs = seen[:24] + _traffic(48, f"new{tag}")[24:]  # as long: the same width
    else:
        reqs = _traffic(48, f"new{tag}")
    tiers, numvals, masks, cached, _keys = tiered(reqs)
    assert tiers[0][6].shape[0] == h
    assert (len(tiers) == 1) == (n_tiers == 1 and not masked)
    assert any(m is not None for m in masks) == masked
    if cache_on:
        assert any(c.any() for c in cached), "no cached hit row rode the window"
    else:
        assert cached is None

    staged = stage_window(tiers, numvals, cached)
    hits = []
    for tier, slab, mask in zip(staged.tiers, staged.match_slabs, masks):
        want = eng._host_tier_hits(tier, mask)
        got = np.asarray(match_tier_packed(eng.model, slab, mask=mask))
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want)
        hits.append(want)
    want = eng._host_post(
        tuple(hits), eng._tier_pairs(staged.tiers), staged.numvals, 2, staged.cached
    )
    got = np.asarray(eval_post_tiered(
        eng.model, tuple(hits), staged.post_slab, max_phase=2,
        layout=post_layout(tiers, numvals, cached),
    ))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want)
    assert want[:, 0].any() and not want[: len(reqs), 0].all()  # both verdicts occur


def test_stage_window_refuses_another_dtype():
    """The slabs hold ``uint8`` rows and ``int32`` the rest: an operand
    of another dtype is refused, never cast in silence."""
    eng = _engine(1, False)
    tensors = eng._tensorize([eng.extractor.extract(r) for r in _traffic(6, "dtype")])
    tiers, numvals, _masks = eng.tier(tensors)
    stage_window(tiers, numvals, None).release()
    bad = ((tiers[0][0].astype(np.int8),) + tiers[0][1:],) + tiers[1:]
    with pytest.raises(TypeError):
        stage_window(bad, numvals, None)


# -- (c) what prewarm compiles is what a window launches ------------------------


def _counters():
    s = EXEC_CACHE.stats()
    return {k: s[k] for k in (
        "misses", "bypasses", "host_twin_windows", "launch_plan_misses",
        "launch_plan_hits", "device_windows")}


@pytest.mark.parametrize("path", ["blob", "requests"])
def test_warmed_engine_serves_every_prewarmed_shape_without_minting(path):
    """``prewarm`` shapes its placeholders from the slab layout
    (``_tier_specs``): the window of the same shape launches the
    executables it compiled, on the native path and the per-request
    path alike, and once each shape was seen nothing is resolved
    again."""
    eng = WafEngine(_RULES[2])  # its own engine: an empty launch table
    assert eng._native.tiered
    batches = [_traffic(n, f"warm{path}{n}") for n in (1, 6, 40)]

    def serve(batch):
        if path == "blob":
            return eng.collect(eng.prepare_blob(serialize_requests(batch), len(batch)))
        return eng.collect(eng.prepare(batch))

    for batch in batches:
        eng.prewarm(batch)
    warmed = _counters()
    first = [serve(batch) for batch in batches]
    seen = _counters()
    for flat in ("misses", "bypasses", "host_twin_windows"):
        assert seen[flat] == warmed[flat], flat
    assert seen["device_windows"] - warmed["device_windows"] == len(batches)
    # The value cache now holds the batches' rows: another window shape
    # each (cached buckets). Serve them until no shape is new.
    for _ in range(2):
        again = [serve(batch) for batch in batches]
    settled = _counters()
    again = [serve(batch) for batch in batches]
    after = _counters()
    for flat in ("misses", "bypasses", "host_twin_windows", "launch_plan_misses"):
        assert after[flat] == settled[flat], flat
    assert after["launch_plan_hits"] - settled["launch_plan_hits"] == len(batches)
    for a, b in zip(first, again):
        assert [(v.interrupted, v.rule_id) for v in a] == [
            (v.interrupted, v.rule_id) for v in b]
    assert any(v.interrupted for vs in again for v in vs)


# -- (d) host operands a window ----------------------------------------------------

# A prefiltered group (384 exact states: approximated on the device,
# confirmed on the host) beside a plain one.
_PREFILTER_RULES = (
    "SecRuleEngine On\n"
    'SecRule ARGS "@rx (a|bc)*a(a|bc){7}d" "id:8001,phase:2,deny,status:403"\n'
    'SecRule ARGS "@contains evilmonkey" "id:8002,phase:2,deny,status:403"\n'
)


@pytest.mark.parametrize("rules,repacks", [(_RULES[1], False), (_PREFILTER_RULES, True)],
                         ids=["plain", "confirm-repacks"])
def test_host_operands_a_native_window(monkeypatch, rules, repacks):
    """A native window hands the device one match slab a tier and one
    post slab; a tier whose hit rows the prefilter confirm repacked
    hands those over too: at most 2 x tiers + 1 host arrays a window."""
    if repacks:  # or the second window's bait is a cached, confirmed row
        monkeypatch.setenv("CKO_VALUE_CACHE_MB", "0")
    eng = WafEngine(rules)
    assert eng._native.tiered
    if repacks:
        assert eng.model.prefilter_cols
        # The approximation's bait: a device positive the exact DFA clears.
        reqs = [HttpRequest(uri=f"/?q=bcbcbcbcd&n={i}") for i in range(6)]
    else:
        reqs = _traffic(12, "operands")
    blob = serialize_requests(reqs)
    eng.collect(eng.prepare_blob(blob, len(reqs)))  # compiles; fills the value cache
    reqs = [HttpRequest(uri=r.uri + "&fresh=1", headers=r.headers, method=r.method,
                        body=r.body) for r in reqs]
    blob = serialize_requests(reqs)
    before = eng.tiering_summary()
    fp_before = eng.prefilter_stats["false_positives"]
    verdicts = eng.collect(eng.prepare_blob(blob, len(reqs)))
    after = eng.tiering_summary()
    windows = after["windows"] - before["windows"]
    tiers = after["tiers"] - before["tiers"]
    operands = after["host_operands"] - before["host_operands"]
    assert windows == 1 and tiers >= 1
    if repacks:
        assert eng.prefilter_stats["false_positives"] > fp_before
        assert not any(v.interrupted for v in verdicts)
        assert tiers + 1 < operands <= 2 * tiers + 1
    else:
        assert operands == tiers + 1
