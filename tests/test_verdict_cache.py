"""Fingerprint verdict cache + in-window row dedup (sidecar/verdict_cache.py).

Pins the repeat-traffic fast path's invariants:

- correctness bar: a cache hit's verdict is BIT-IDENTICAL to the
  uncached verdict — same status, same x-waf-* attribution, same body
  bytes — cache-cold vs cache-hot on all three frontends (threaded,
  async ingest, ext_proc);
- bounds: LRU capacity eviction and TTL expiry; a hit refreshes
  recency, never lifetime; ``CKO_VERDICT_CACHE_MAX=0`` disables;
- in-window dedup: identical-fingerprint rows dispatch ONE device row,
  the verdict scatters back to every requester's future;
- invalidation: wholesale on every engine swap (reload / forced
  rollback / warm restore), per-fingerprint when the quarantine
  isolates an offender (a cached allow must not outlive quarantine),
  and the operator flush endpoint on both HTTP frontends;
- bypass: quarantine-matched, deadline-header, and trusted-tenant
  requests never consult the cache.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar
from coraza_kubernetes_operator_tpu.sidecar.batcher import MicroBatcher
from coraza_kubernetes_operator_tpu.sidecar.quarantine import fingerprint
from coraza_kubernetes_operator_tpu.sidecar.verdict_cache import VerdictCache

REPO = Path(__file__).resolve().parent.parent

BASE = """
SecRuleEngine On
SecRequestBodyAccess On
SecDefaultAction "phase:2,log,deny,status:403"
"""
EVIL_MONKEY = (
    'SecRule ARGS|REQUEST_URI "@contains evilmonkey" '
    '"id:3001,phase:2,deny,status:403"\n'
)


@pytest.fixture(scope="module")
def engine():
    return WafEngine(BASE + EVIL_MONKEY)


def _sidecar(engine=None, frontend="threaded", **kw) -> TpuEngineSidecar:
    config = SidecarConfig(
        host="127.0.0.1",
        port=0,
        max_batch_size=kw.pop("max_batch_size", 64),
        max_batch_delay_ms=kw.pop("max_batch_delay_ms", 1.0),
        frontend=frontend,
        **kw,
    )
    return TpuEngineSidecar(config, engine=engine)


def _wait(predicate, timeout_s=60.0, interval_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return False


def _http(port, path, method="GET", body=None, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        method=method,
        data=body,
        headers=headers or {},
    )
    try:
        resp = urllib.request.urlopen(req, timeout=30)
        return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _verdict_tuple(status, headers, body):
    return (
        status,
        headers.get("x-waf-action"),
        headers.get("x-waf-rule-id"),
        body,
    )


# -- unit: bounds, freezing, invalidation -------------------------------------


def test_lru_capacity_eviction_and_hit_recency():
    vc = VerdictCache(max_entries=2, ttl_s=60.0)
    vc.insert(None, "u", "fp1", "v1")
    vc.insert(None, "u", "fp2", "v2")
    assert vc.lookup(None, "u", "fp1") == "v1"  # fp1 now most-recent
    vc.insert(None, "u", "fp3", "v3")  # evicts fp2 (LRU), not fp1
    assert vc.evictions_total == 1
    assert vc.lookup(None, "u", "fp2") is None
    assert vc.lookup(None, "u", "fp1") == "v1"
    assert vc.lookup(None, "u", "fp3") == "v3"
    assert len(vc) == 2


def test_ttl_expiry_not_refreshed_by_hits():
    vc = VerdictCache(max_entries=8, ttl_s=0.15)
    vc.insert(None, "u", "fp", "v")
    assert vc.lookup(None, "u", "fp") == "v"
    # Keep hitting: recency refreshes, TTL must NOT — the entry still
    # dies at its insertion-bounded lifetime.
    time.sleep(0.08)
    assert vc.lookup(None, "u", "fp") == "v"
    time.sleep(0.1)
    assert vc.lookup(None, "u", "fp") is None
    assert len(vc) == 0


def test_disabled_when_max_entries_zero(monkeypatch):
    monkeypatch.setenv("CKO_VERDICT_CACHE_MAX", "0")
    vc = VerdictCache()
    assert vc.enabled is False
    vc.insert(None, "u", "fp", "v")
    assert vc.lookup(None, "u", "fp") is None
    assert len(vc) == 0
    monkeypatch.setenv("CKO_VERDICT_CACHE_MAX", "17")
    monkeypatch.setenv("CKO_VERDICT_CACHE_TTL_S", "9.5")
    vc = VerdictCache()
    assert vc.enabled and vc.max_entries == 17 and vc.ttl_s == 9.5


def test_insert_freezes_a_copy():
    vc = VerdictCache(max_entries=4, ttl_s=60.0)
    verdict = {"status": 200, "tags": ["a"]}
    vc.insert(None, "u", "fp", verdict)
    verdict["tags"].append("mutated-after-insert")
    frozen = vc.lookup(None, "u", "fp")
    assert frozen == {"status": 200, "tags": ["a"]}


def test_uuid_keying_and_wholesale_invalidation():
    vc = VerdictCache(max_entries=8, ttl_s=60.0)
    vc.insert(None, "uuid-old", "fp", "old-verdict")
    # Same fingerprint under a new ruleset uuid: never answered by the
    # old entry (defense in depth under the wholesale swap drop).
    assert vc.lookup(None, "uuid-new", "fp") is None
    vc.insert(None, "uuid-new", "fp", "new-verdict")
    assert vc.invalidate_all() == 2
    assert vc.invalidations_total == 2
    assert vc.lookup(None, "uuid-new", "fp") is None


def test_evict_fingerprint_spans_uuids_and_tenants():
    vc = VerdictCache(max_entries=8, ttl_s=60.0)
    vc.insert(None, "u1", "fp", "v1")
    vc.insert(None, "u2", "fp", "v2")
    vc.insert(None, "u1", "other", "v3")
    assert vc.evict_fingerprint("fp") == 2
    assert vc.lookup(None, "u1", "other") == "v3"
    assert vc.invalidations_total == 2


# -- batcher: per-request hits, in-window dedup, bypass -----------------------


class _CountingEngine:
    """Stub engine recording exactly which rows reach the device."""

    warmed = True

    def __init__(self):
        self.batches = []

    def evaluate(self, reqs):
        self.batches.append([r.uri for r in reqs])
        return [("verdict", r.uri) for r in reqs]

    @property
    def rows_evaluated(self):
        return sum(len(b) for b in self.batches)


def _batcher(eng, **kw):
    b = MicroBatcher(
        lambda: eng,
        max_batch_size=kw.pop("max_batch_size", 16),
        max_batch_delay_ms=kw.pop("max_batch_delay_ms", 0),
    )
    b.verdict_cache = VerdictCache(max_entries=64, ttl_s=60.0)
    return b


def test_repeat_request_served_without_device_row():
    eng = _CountingEngine()
    b = _batcher(eng)
    b.start()
    try:
        first = b.evaluate(HttpRequest(uri="/hot"), timeout_s=10)
        assert eng.rows_evaluated == 1
        second = b.evaluate(HttpRequest(uri="/hot"), timeout_s=10)
        assert second == first == ("verdict", "/hot")
        assert eng.rows_evaluated == 1  # the repeat never reached the device
        assert b.verdict_cache.hits_total == 1
        assert b.verdict_cache.misses_total == 1
    finally:
        b.stop()


def test_in_window_dedup_scatters_to_all_requesters():
    """Mixed window: duplicates of one fingerprint plus unique rows.
    The device sees each fingerprint ONCE; every future still resolves
    to the right verdict."""
    eng = _CountingEngine()
    b = _batcher(eng, max_batch_size=8, max_batch_delay_ms=200.0)
    b.start()
    try:
        dup = HttpRequest(uri="/dup")
        futs = [
            b.submit(dup),
            b.submit(HttpRequest(uri="/a")),
            b.submit(HttpRequest(uri="/dup")),  # same fingerprint, new object
            b.submit(HttpRequest(uri="/b")),
            b.submit(dup),
        ]
        results = [f.result(timeout=10) for f in futs]
        assert results[0] == results[2] == results[4] == ("verdict", "/dup")
        assert results[1] == ("verdict", "/a")
        assert results[3] == ("verdict", "/b")
        # One window, three unique fingerprints on the device.
        assert eng.batches == [["/dup", "/a", "/b"]]
        assert b.window_dedup_rows == 2
        # Every eligible row counts a lookup miss (dedup happens after
        # the lookup); device rows = misses - dedup_rows.
        assert b.verdict_cache.misses_total == 5
    finally:
        b.stop()


def test_tenant_rows_probe_their_engines_rule_set_and_deadline_rows_bypass_cache():
    """A tenant's rows are cache-eligible like the default tenant's,
    under the rule set of the engine that serves them (PR 33: before,
    only tenant None was); a deadline row still never touches it."""
    eng = _CountingEngine()
    b = _batcher(eng)
    b.start()
    try:
        for _ in range(2):
            b.submit(HttpRequest(uri="/t"), tenant="ns/name").result(timeout=10)
        vc = b.verdict_cache
        assert eng.rows_evaluated == 1  # the repeat was a hit
        assert vc.hits_total == 1 and vc.misses_total == 1 and len(vc) == 1
        for _ in range(2):
            b.submit(HttpRequest(uri="/d"), no_cache=True).result(timeout=10)
        assert eng.rows_evaluated == 3  # every deadline row rode the device
        assert vc.hits_total == 1 and vc.misses_total == 1 and len(vc) == 1
    finally:
        b.stop()


def test_cache_disabled_batcher_path_unchanged():
    eng = _CountingEngine()
    b = MicroBatcher(lambda: eng, max_batch_size=4, max_batch_delay_ms=0)
    b.verdict_cache = VerdictCache(max_entries=0)
    b.start()
    try:
        for _ in range(3):
            assert b.evaluate(HttpRequest(uri="/x"), timeout_s=10) == (
                "verdict",
                "/x",
            )
        assert eng.rows_evaluated == 3
        assert b.window_dedup_rows == 0
    finally:
        b.stop()


# -- sidecar wiring: quarantine interop, swap invalidation, flush -------------


def test_quarantine_add_evicts_cached_verdict(engine):
    """Regression for the latent interaction: a verdict cached BEFORE
    its fingerprint is quarantined must not keep serving after — the
    registry's on_add hook evicts the entry."""
    sc = _sidecar(engine)
    req = HttpRequest(method="POST", uri="/p", body=b"x=1")
    fp = fingerprint(req)
    sc.verdict_cache.insert(None, "u", fp, "stale-allow")
    sc.verdict_cache.insert(None, "u", "other-fp", "keep")
    sc.quarantine.add(fp)
    assert sc.verdict_cache.lookup(None, "u", fp) is None
    assert sc.verdict_cache.lookup(None, "u", "other-fp") == "keep"
    assert sc.verdict_cache.invalidations_total >= 1


def test_engine_swap_invalidates_wholesale(engine):
    """Every ruleset swap path (reload, rollout promotion, forced
    rollback, warm restore) funnels through the sidecar's on_swap hook;
    the cache must drop everything it holds."""
    sc = _sidecar(engine)
    sc.verdict_cache.insert(None, "u", "fp1", "v1")
    sc.verdict_cache.insert(None, "u", "fp2", "v2")
    sc._on_engine_swap(engine)
    assert len(sc.verdict_cache) == 0
    assert sc.verdict_cache.invalidations_total == 2
    # The reloader hook is actually wired to this method.
    assert sc.tenants._on_swap is not None


@pytest.mark.parametrize("frontend", ["threaded", "async"])
def test_flush_endpoint_and_stats_block(engine, frontend):
    sc = _sidecar(engine, frontend=frontend)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        cold = _http(sc.port, "/?q=repeat")
        hot = _http(sc.port, "/?q=repeat")
        assert _verdict_tuple(*cold) == _verdict_tuple(*hot)
        assert _wait(lambda: sc.verdict_cache.hits_total >= 1, 10), frontend
        entries_before = len(sc.verdict_cache)
        assert entries_before >= 1
        status, _, body = _http(
            sc.port, "/waf/v1/cache/flush", method="POST", body=b""
        )
        assert status == 200
        out = json.loads(body)
        assert out["flushed"] == entries_before and out["entries"] == 0
        assert len(sc.verdict_cache) == 0
        st = sc.stats()["verdict_cache"]
        assert st["enabled"] is True
        assert st["flushes"] == 1
        assert st["hits_total"] >= 1
        assert "window_dedup_rows" in st
        _, _, metrics = _http(sc.port, "/waf/v1/metrics")
        for name in (
            b"cko_verdict_cache_entries",
            b"cko_verdict_cache_hits_total",
            b"cko_verdict_cache_misses_total",
            b"cko_verdict_cache_invalidations_total",
            b"cko_window_dedup_rows_total",
        ):
            assert name in metrics, name
    finally:
        sc.stop()


def test_deadline_header_request_bypasses_cache(engine):
    sc = _sidecar(engine)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        before = sc.verdict_cache.stats()
        for _ in range(2):
            status, _, _ = _http(
                sc.port,
                "/?q=deadline",
                headers={"X-CKO-Deadline-Ms": "5000"},
            )
            assert status == 200
        after = sc.verdict_cache.stats()
        assert after["hits_total"] == before["hits_total"]
        assert after["misses_total"] == before["misses_total"]
        assert len(sc.verdict_cache) == 0
    finally:
        sc.stop()


# -- cache-cold vs cache-hot verdict parity on all three frontends ------------


@pytest.mark.slow
def test_ftw_corpus_cold_vs_hot_parity_all_frontends():
    """The correctness bar, measured: replay the bundled ftw corpus
    cache-cold, then replay it again cache-hot, on the threaded + async
    HTTP frontends and the ext_proc data plane. Every verdict tuple
    (status, x-waf-action, x-waf-rule-id, body bytes) must be
    bit-identical hot-vs-cold AND across frontends."""
    from test_ingest import (
        _corpus_stage_requests,
        _extproc_corpus_verdicts,
        _norm_verdict,
        _raw,
    )

    rules = (REPO / "ftw" / "rules" / "base.conf").read_text() + (
        REPO / "ftw" / "rules" / "crs-mini.conf"
    ).read_text()
    eng = WafEngine(rules)
    stages = _corpus_stage_requests()
    assert len(stages) >= 10
    cold, hot = {}, {}
    for frontend in ("threaded", "async"):
        extproc = (
            {"extproc_port": 0, "extproc_impl": "native"}
            if frontend == "async"
            else {}
        )
        sc = _sidecar(eng, frontend=frontend, **extproc)
        sc.start()
        try:
            assert _wait(sc.ready)
            assert _wait(lambda: sc.serving_mode() == "promoted", timeout_s=120)

            def _replay():
                got = []
                for title, raw_bytes, _req in stages:
                    (resp,) = _raw(sc.port, raw_bytes, 1)
                    assert resp is not None, (frontend, title)
                    status, headers, body = resp
                    got.append(
                        (
                            title,
                            status,
                            headers.get("x-waf-action"),
                            headers.get("x-waf-rule-id"),
                            body,
                        )
                    )
                return got

            cold[frontend] = _replay()
            hits_after_cold = sc.verdict_cache.hits_total
            hot[frontend] = _replay()
            # The hot pass genuinely exercised the cache.
            assert sc.verdict_cache.hits_total > hits_after_cold, frontend
            if frontend == "async":
                hot["extproc"] = _extproc_corpus_verdicts(sc, stages)
        finally:
            sc.stop()
    # Hot == cold per frontend (bit-identical verdicts), and the two
    # HTTP frontends agree with each other.
    assert hot["threaded"] == cold["threaded"]
    assert hot["async"] == cold["async"]
    assert hot["async"] == hot["threaded"]
    # ext_proc (cache-hot) against the HTTP frontends, normalized the
    # same way the tri-parity test normalizes allow bodies.
    normalized = {
        leg: [_norm_verdict(*v) for v in hot[leg]]
        for leg in ("threaded", "async", "extproc")
    }
    assert normalized["extproc"] == normalized["async"] == normalized["threaded"]
    actions = {v[2] for v in hot["async"]}
    assert "deny" in actions and "allow" in actions


def test_concurrent_identical_requests_one_device_row(engine):
    """End-to-end dedup through a real frontend: a burst of identical
    requests lands in one window; the device answers one row, everyone
    gets the same verdict."""
    sc = _sidecar(engine, max_batch_size=32, max_batch_delay_ms=40.0)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        results = [None] * 8

        def one(i):
            results[i] = _http(sc.port, "/?pet=evilmonkey&burst=1")

        threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        tuples = {_verdict_tuple(*r) for r in results}
        assert len(tuples) == 1
        status, action, rule_id, _body = tuples.pop()
        assert status == 403 and action == "deny" and rule_id == "3001"
        st = sc.stats()["verdict_cache"]
        assert st["hits_total"] + st["window_dedup_rows"] >= 1
    finally:
        sc.stop()
