"""Degraded-mode serving: the "a verdict is always returned" invariant.

Covers the ISSUE 1 acceptance criteria with the fault-injection harness
(``coraza_kubernetes_operator_tpu/testing/faults.py``):

- compile stall (CKO_FAULT_COMPILE_STALL_S) → first verdict in <2s from
  the host fallback while the device path is still "compiling";
- device fault storm (CKO_FAULT_DEVICE_ERROR_RATE) → circuit breaker
  opens, serving demotes to fallback, verdicts keep flowing;
- failurePolicy enforcement when the breaker is open AND no fallback is
  available: fail → 403-by-default, allow → pass-through with
  ``cko_failopen_total`` incremented — never a blank 500;
- reload mid-storm → no blank 500s, no stale-version verdicts;
- host fallback verdicts are bit-identical to the device path's, on the
  synthetic corpus and on ftw crs-lite corpus traffic;
- deadline propagation (X-CKO-Deadline-Ms) and 429 load shedding.

The CI ``degraded-mode`` job runs this file with an ambient
CKO_FAULT_COMPILE_STALL_S=30; tests that need a different stall set it
explicitly (monkeypatch wins over the ambient knob).
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future

import pytest

from coraza_kubernetes_operator_tpu.cache import RuleSetCache, RuleSetCacheServer
from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar
from coraza_kubernetes_operator_tpu.sidecar.degraded import (
    BREAKER_CLOSED,
    BREAKER_OPEN,
    CircuitBreaker,
)
from coraza_kubernetes_operator_tpu.sidecar.reloader import RuleReloader
from coraza_kubernetes_operator_tpu.testing import faults

BASE = """
SecRuleEngine On
SecRequestBodyAccess On
SecDefaultAction "phase:2,log,deny,status:403"
"""
EVIL_MONKEY = (
    'SecRule ARGS|REQUEST_URI "@contains evilmonkey" '
    '"id:3001,phase:2,deny,status:403"\n'
)
EVIL_PANDA = (
    'SecRule ARGS|REQUEST_URI "@contains evilpanda" '
    '"id:3002,phase:2,deny,status:403"\n'
)
KEY = "default/ruleset"


def _sidecar(engine=None, **kw) -> TpuEngineSidecar:
    cfg = SidecarConfig(host="127.0.0.1", port=0, **kw)
    return TpuEngineSidecar(cfg, engine=engine)


def _http(port, path, method="GET", body=None, headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        method=method,
        data=body,
        headers=headers or {},
    )
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _wait(predicate, timeout_s=60.0, interval_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return False


def _verdict_tuple(v):
    return (v.interrupted, v.status, v.rule_id, tuple(v.matched_ids), tuple(sorted(v.scores.items())))


# -- fault harness unit tests ------------------------------------------------


def test_fault_knobs(monkeypatch):
    monkeypatch.delenv("CKO_FAULT_COMPILE_STALL_S", raising=False)
    assert faults.injected_compile_stall_s() == 0.0
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "2.5")
    assert faults.injected_compile_stall_s() == 2.5
    monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "0")
    assert not faults.injected_device_error()
    monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "1.0")
    assert faults.injected_device_error()
    with pytest.raises(faults.DeviceFault):
        faults.on_device_dispatch(warmed=True)
    monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "0")
    faults.on_device_dispatch(warmed=True)  # no-op again


def test_circuit_breaker_state_machine():
    br = CircuitBreaker(threshold=3, cooldown_s=0.1)
    assert br.state == BREAKER_CLOSED
    assert not br.record_failure()
    assert not br.record_failure()
    assert br.record_failure()  # third consecutive opens
    assert br.state == BREAKER_OPEN
    assert not br.allow_probe()  # cooldown not elapsed
    time.sleep(0.15)
    assert br.allow_probe()  # half-open: one probe granted
    assert br.record_failure()  # probe failed -> reopens
    assert br.state == BREAKER_OPEN
    time.sleep(0.15)
    assert br.allow_probe()
    br.record_success()
    assert br.state == BREAKER_CLOSED


def test_reloader_backoff_and_cache_outage(monkeypatch):
    cache = RuleSetCache()
    cache.put(KEY, BASE + EVIL_MONKEY)
    srv = RuleSetCacheServer(cache, host="127.0.0.1", port=0)
    srv.start()
    try:
        r = RuleReloader(
            f"http://127.0.0.1:{srv.port}", KEY, poll_interval_s=15.0
        )
        monkeypatch.setenv("CKO_FAULT_CACHE_OUTAGE", "1")
        assert not r.poll_once()
        assert not r.poll_once()
        assert r.poll_failures == 2
        assert r.consecutive_poll_failures == 2
        # Failure backoff retries well before the 15s poll interval
        # (base 1.0s for two consecutive failures, ±20% jitter).
        assert r.next_wait_s() <= 1.2
        monkeypatch.setenv("CKO_FAULT_CACHE_OUTAGE", "0")
        assert r.poll_once()  # outage over: the ruleset loads
        assert r.engine is not None
        assert r.consecutive_poll_failures == 0
        # Healthy waits are the poll interval ±20% jitter (thundering-herd
        # decorrelation), and genuinely vary call to call.
        waits = [r.next_wait_s() for _ in range(16)]
        assert all(15.0 * 0.8 <= w <= 15.0 * 1.2 for w in waits), waits
        assert len({round(w, 6) for w in waits}) > 1
    finally:
        srv.stop()


# -- compile stall: the headline invariant -----------------------------------


def test_compile_stall_first_verdict_under_2s(monkeypatch):
    """ISSUE 1 acceptance: with a 60s compile stall injected, the sidecar
    serves its first verdict in <2s of the first request (host fallback),
    and the serving mode reports 'fallback'."""
    stall = os.environ.get("CKO_FAULT_COMPILE_STALL_S") or "60"
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", stall)
    engine = WafEngine(BASE + EVIL_MONKEY)
    sc = _sidecar(engine)
    sc.start()
    try:
        t0 = time.monotonic()
        status, headers, _ = _http(sc.port, "/?pet=evilmonkey")
        first_verdict_s = time.monotonic() - t0
        assert status == 403
        assert headers["x-waf-action"] == "deny"
        assert headers["x-waf-rule-id"] == "3001"
        assert first_verdict_s < 2.0, first_verdict_s
        status, headers, _ = _http(sc.port, "/?q=hello")
        assert status == 200
        assert sc.serving_mode() == "fallback"
        assert sc.stats()["degraded"]["fallback_requests"] >= 2
        _, _, metrics = _http(sc.port, "/waf/v1/metrics")
        assert b"cko_serving_mode 1" in metrics
        assert b"cko_fallback_requests_total 2" in metrics
    finally:
        sc.stop()


def test_promotion_lands_and_batcher_takes_over(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE + EVIL_MONKEY)
    sc = _sidecar(engine)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        status, _, _ = _http(sc.port, "/?pet=evilmonkey")
        assert status == 403
        assert sc.batcher.stats.requests >= 1
        assert sc.stats()["degraded"]["promotions"] == 1
    finally:
        sc.stop()


def test_bulk_reports_serving_mode(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "60")
    engine = WafEngine(BASE + EVIL_MONKEY)
    sc = _sidecar(engine)
    sc.start()
    try:
        payload = json.dumps(
            {"requests": [{"uri": "/?a=evilmonkey"}, {"uri": "/ok"}]}
        ).encode()
        status, _, body = _http(sc.port, "/waf/v1/evaluate", method="POST", body=payload)
        assert status == 200, body
        out = json.loads(body)
        assert out["mode"] == "fallback"
        assert out["verdicts"][0]["interrupted"] is True
        assert out["verdicts"][0]["status"] == 403
        assert out["verdicts"][1]["interrupted"] is False
    finally:
        sc.stop()


# -- device fault storm: breaker + demotion ----------------------------------


def test_device_fault_storm_opens_breaker_and_serves_fallback(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE + EVIL_MONKEY)
    sc = _sidecar(engine, breaker_threshold=3, breaker_cooldown_s=300.0)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "1.0")
        statuses = []
        for i in range(6):
            status, _, _ = _http(sc.port, f"/?pet=evilmonkey&i={i}")
            statuses.append(status)
        # Every request in the storm still got a correct verdict.
        assert statuses == [403] * 6
        assert sc.degraded.breaker.state == BREAKER_OPEN
        assert sc.serving_mode() == "broken"
        _, _, metrics = _http(sc.port, "/waf/v1/metrics")
        assert b"cko_breaker_state 1" in metrics
        assert b"cko_serving_mode 3" in metrics
        # Benign traffic still flows (fallback), no 500s anywhere.
        status, _, _ = _http(sc.port, "/?q=fine")
        assert status == 200
    finally:
        monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "0")
        sc.stop()


def test_breaker_recloses_after_cooldown(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE + EVIL_MONKEY)
    sc = _sidecar(engine, breaker_threshold=2, breaker_cooldown_s=0.2)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "1.0")
        for i in range(3):
            _http(sc.port, f"/?pet=evilmonkey&i={i}")
        assert sc.degraded.breaker.state == BREAKER_OPEN
        # Storm over: the half-open probe re-proves the device path and
        # the breaker closes (mode returns to promoted).
        monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "0")
        _http(sc.port, "/?q=kick")  # route() kicks the probe
        assert _wait(lambda: sc.serving_mode() == "promoted", timeout_s=30)
    finally:
        sc.stop()


# -- failurePolicy under faults (no fallback available) ----------------------


def _storm_no_fallback(monkeypatch, failure_policy):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE + EVIL_MONKEY)
    engine.warmed = True  # device-routed from the first request
    sc = _sidecar(
        engine,
        fallback_enabled=False,
        breaker_threshold=2,
        breaker_cooldown_s=300.0,
        failure_policy=failure_policy,
    )
    sc.start()
    monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "1.0")
    statuses = []
    try:
        for i in range(6):
            status, headers, body = _http(sc.port, f"/?pet=evilmonkey&i={i}")
            statuses.append((status, headers.get("x-waf-action"), body))
        return sc, statuses
    finally:
        monkeypatch.setenv("CKO_FAULT_DEVICE_ERROR_RATE", "0")
        sc.stop()


def test_failure_policy_fail_closed_on_breaker_open(monkeypatch):
    """fail → 403-by-default once the breaker is open; never a blank 500."""
    sc, statuses = _storm_no_fallback(monkeypatch, "fail")
    assert sc.degraded.breaker.state == BREAKER_OPEN
    for status, action, body in statuses:
        assert status in (403, 503), (status, body)
        assert action == "fail-closed"
        assert body  # never blank
    # Once open, the policy answer is a deny (403), not an error.
    assert statuses[-1][0] == 403


def test_failure_policy_fail_open_on_breaker_open(monkeypatch):
    """allow → pass-through with cko_failopen_total incremented."""
    sc, statuses = _storm_no_fallback(monkeypatch, "allow")
    assert sc.degraded.breaker.state == BREAKER_OPEN
    for status, action, body in statuses:
        assert status == 200, (status, body)
        assert action == "fail-open"
        assert body  # never blank
    assert sc.stats()["failopen_total"] >= len(statuses)


# -- deadline propagation + load shedding ------------------------------------


def test_deadline_header_falls_back_when_device_misses_it(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE + EVIL_MONKEY)
    engine.warmed = True
    sc = _sidecar(engine)
    sc.start()
    try:
        # Wedge the device path: futures never resolve.
        sc.batcher.submit = (
            lambda request, tenant=None, span=None, lane=None, no_cache=False: (
                Future()
            )
        )
        t0 = time.monotonic()
        status, _, _ = _http(
            sc.port,
            "/?pet=evilmonkey",
            headers={"X-CKO-Deadline-Ms": "400"},
        )
        elapsed = time.monotonic() - t0
        assert status == 403  # fallback answered inside the deadline path
        assert elapsed < 5.0, elapsed
    finally:
        sc.stop()


def test_load_shedding_429(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE + EVIL_MONKEY)
    engine.warmed = True
    engine._native._ctx = None  # bulk must take the batcher path
    sc = _sidecar(engine, queue_budget=8, shed_retry_after_s=2.0)
    sc.start()
    try:
        sc.batcher.pending = lambda lane=None: 100  # backlog over budget
        status, headers, body = _http(sc.port, "/?pet=evilmonkey")
        assert status == 429
        # Live queue-depth Retry-After: 100/8 caps at 8x the 2.0s base.
        assert headers["Retry-After"] == "16"
        assert headers["x-waf-action"] == "shed"
        payload = json.dumps({"requests": [{"uri": "/x"}]}).encode()
        status, headers, body = _http(
            sc.port, "/waf/v1/evaluate", method="POST", body=payload
        )
        assert status == 429
        assert "overloaded" in json.loads(body)["error"]
        assert sc.stats()["shed_total"] >= 2
        _, _, metrics = _http(sc.port, "/waf/v1/metrics")
        assert b"cko_shed_total 2" in metrics
    finally:
        sc.stop()


# -- reload mid-storm ---------------------------------------------------------


def test_reload_mid_storm_no_blank_500_no_stale_verdicts(monkeypatch):
    monkeypatch.setenv(
        "CKO_FAULT_COMPILE_STALL_S",
        os.environ.get("CKO_FAULT_COMPILE_STALL_S") or "60",
    )
    cache = RuleSetCache()
    cache.put(KEY, BASE + EVIL_MONKEY)
    srv = RuleSetCacheServer(cache, host="127.0.0.1", port=0)
    srv.start()
    sc = TpuEngineSidecar(
        SidecarConfig(
            host="127.0.0.1",
            port=0,
            cache_base_url=f"http://127.0.0.1:{srv.port}",
            instance_key=KEY,
            poll_interval_s=0.05,
        )
    )
    sc.start()
    stop = threading.Event()
    bad: list = []

    def storm():
        i = 0
        while not stop.is_set():
            status, _, body = _http(sc.port, f"/?pet=evilmonkey&i={i}")
            if status not in (200, 403) or not body:
                bad.append((status, body))
            i += 1

    try:
        assert _wait(sc.ready)
        status, _, _ = _http(sc.port, "/?pet=evilmonkey")
        assert status == 403
        threads = [threading.Thread(target=storm, daemon=True) for _ in range(2)]
        for t in threads:
            t.start()
        cache.put(KEY, BASE + EVIL_PANDA)  # v2: panda blocked, monkey not
        assert _wait(lambda: sc.tenants.total_reloads >= 2, timeout_s=30)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not bad, bad[:5]
        # No stale-version verdicts after the swap.
        status, _, _ = _http(sc.port, "/?pet=evilpanda")
        assert status == 403
        status, headers, _ = _http(sc.port, "/?pet=evilmonkey")
        assert status == 200
    finally:
        stop.set()
        sc.stop()
        srv.stop()


# -- fallback / device verdict parity ----------------------------------------


def test_fallback_parity_synthetic_corpus(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    from coraza_kubernetes_operator_tpu.corpus import (
        synthetic_crs,
        synthetic_requests,
    )

    eng = WafEngine(synthetic_crs(40, seed=3))
    reqs = synthetic_requests(128, attack_ratio=0.3, seed=5)
    dev = eng.evaluate(reqs)
    fb = eng.host_fallback.evaluate(reqs)
    assert [_verdict_tuple(a) for a in dev] == [_verdict_tuple(b) for b in fb]
    assert any(v.interrupted for v in fb)  # the corpus does trip rules


def test_fallback_parity_crs_lite_ftw_corpus(monkeypatch):
    """ISSUE 1 acceptance: fallback verdicts match device verdicts
    byte-for-byte on ftw crs-lite corpus traffic (the SQLi family +
    blocking evaluation)."""
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    from pathlib import Path

    from coraza_kubernetes_operator_tpu.corpus import synthetic_requests
    from coraza_kubernetes_operator_tpu.ftw.corpus import CRS_LITE_DIR
    from coraza_kubernetes_operator_tpu.ftw.loader import load_tests
    from coraza_kubernetes_operator_tpu.ftw.runner import _stage_request

    root = Path(CRS_LITE_DIR)
    text = "\n".join(
        [
            f"SecDataDir {root / 'data'}",
            (root / "crs-setup.conf").read_text(),
            (root / "REQUEST-942-APPLICATION-ATTACK-SQLI.conf").read_text(),
            (root / "REQUEST-949-BLOCKING-EVALUATION.conf").read_text(),
        ]
    )
    eng = WafEngine(text)
    corpus_dir = Path(__file__).resolve().parents[1] / "ftw" / "tests-crs-lite"
    attacks = [
        _stage_request(s)
        for t in load_tests(corpus_dir)
        if str(t.rule_id or "").startswith("942")
        for s in t.stages
        if len(s.data) <= 4096
    ]
    assert attacks, "crs-lite 942 corpus stages missing"
    benign = synthetic_requests(32, attack_ratio=0.0, seed=9)
    reqs = attacks + benign
    dev = eng.evaluate(reqs)
    fb = eng.host_fallback.evaluate(reqs)
    mism = [
        (i, _verdict_tuple(a), _verdict_tuple(b))
        for i, (a, b) in enumerate(zip(dev, fb))
        if _verdict_tuple(a) != _verdict_tuple(b)
    ]
    assert not mism, mism[:3]
    assert sum(v.interrupted for v in fb) > 0


# -- satellite: compiled-ruleset cache ---------------------------------------


def test_compile_rules_cached_roundtrip(tmp_path, monkeypatch):
    from coraza_kubernetes_operator_tpu.compiler import ruleset as rs

    text = BASE + EVIL_MONKEY
    crs1 = rs.compile_rules_cached(text, cache_dir=str(tmp_path))
    pkls = list(tmp_path.glob("*.crs.pkl"))
    assert len(pkls) == 1
    # Second call must be served from the pickle: a compile would blow up.
    def boom(_text):
        raise AssertionError("cache miss: compile_rules called again")

    monkeypatch.setattr(rs, "compile_rules", boom)
    crs2 = rs.compile_rules_cached(text, cache_dir=str(tmp_path))
    assert crs2.n_rules == crs1.n_rules
    assert [r.rule_id for r in crs2.rules] == [r.rule_id for r in crs1.rules]


# -- the launch table's lifetime (ISSUE 29) ----------------------------------


@pytest.mark.parametrize("event", ["reinit_device", "engine_swap", "cache_clear"])
def test_launch_table_is_dropped_with_its_model_and_its_cache(event, monkeypatch):
    """A device re-init, an engine swap and ``EXEC_CACHE.clear()`` each
    leave the next window to resolve its launch again (a lookup in the
    executable cache, or a compile after a clear); the one after it
    launches from the table; verdicts never move."""
    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE

    monkeypatch.setenv("CKO_VALUE_CACHE_MB", "0")  # one window shape
    rules = BASE + EVIL_MONKEY
    reqs = [HttpRequest(uri="/?q=evilmonkey"), HttpRequest(uri="/?q=fine")]

    def verdicts(engine):
        return [(v.interrupted, v.status, v.rule_id) for v in engine.evaluate(reqs)]

    def grew(before):
        after = EXEC_CACHE.stats()
        return {k: after[k] - before[k] for k in
                ("launch_plan_hits", "launch_plan_misses", "misses", "host_twin_windows")}

    eng = WafEngine(rules)
    want = verdicts(eng)
    assert want == [(True, 403, 3001), (False, 200, None)]
    s0 = EXEC_CACHE.stats()
    assert verdicts(eng) == want
    assert grew(s0) == {"launch_plan_hits": 1, "launch_plan_misses": 0,
                        "misses": 0, "host_twin_windows": 0}
    old_sig, old_table = eng._model_sig, eng._launch_table[1]
    assert len(old_table) == 1

    resident = dict(EXEC_CACHE._entries)
    try:
        if event == "reinit_device":
            eng.reinit_device()
            assert eng._model_sig == old_sig and eng._model_sig is not old_sig
        elif event == "engine_swap":
            eng = WafEngine(rules)  # what every swap path serves next
        else:
            EXEC_CACHE.clear()
            assert len(EXEC_CACHE) == 0
        s1 = EXEC_CACHE.stats()
        assert verdicts(eng) == want
        first = grew(s1)
        assert first["launch_plan_hits"] == 0 and first["launch_plan_misses"] == 1
        assert first["host_twin_windows"] == 0
        # Only the cleared cache has anything to compile.
        assert (first["misses"] > 0) == (event == "cache_clear")
        assert eng._launch_table[1] is not old_table
        assert len(eng._launch_table[1]) == 1
        s2 = EXEC_CACHE.stats()
        assert verdicts(eng) == want
        assert grew(s2) == {"launch_plan_hits": 1, "launch_plan_misses": 0,
                            "misses": 0, "host_twin_windows": 0}
    finally:
        # Other tests of this process keep the executables they minted.
        for key, compiled in resident.items():
            EXEC_CACHE._entries.setdefault(key, compiled)
