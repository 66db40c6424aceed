"""Poison-request quarantine + dispatch watchdog (per-request fault
isolation for the batched device path).

Pins the fault-taxonomy invariants (docs/DEGRADED_MODE.md):

- a poison request that faults its window is bisected, fingerprinted,
  and quarantined — future copies are routed to host fallback at
  batch-assembly time and the breaker never opens for it;
- the isolation invariant: a faulted request never changes a
  NEIGHBOR's verdict (everyone in the window still gets the exact
  verdict the ruleset assigns);
- a blown window deadline ABANDONS the window (futures re-answered by
  the server's rescue paths — real verdicts, zero lost), parks the
  stuck readback, and the collector keeps serving;
- loss-class errors during an abandoned window reach the
  DeviceLossManager, not the transient breaker;
- the collector-leak fix: a wedged collector is flagged loudly at
  stop() instead of leaking silently.
"""

import threading
import time
import urllib.error
import urllib.request

import pytest

from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar
from coraza_kubernetes_operator_tpu.sidecar.batcher import (
    MicroBatcher,
    WindowAbandoned,
)
from coraza_kubernetes_operator_tpu.sidecar.degraded import BREAKER_CLOSED
from coraza_kubernetes_operator_tpu.sidecar.quarantine import (
    PoisonBisector,
    QuarantineRegistry,
    fingerprint,
)
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
MARKER = "POISON-X"


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


def _poison(uri="/", body=b"a=POISON-X"):
    return HttpRequest(method="POST", uri=uri, body=body)


# -- fault-harness knobs ------------------------------------------------------


def test_poison_marker_knob(monkeypatch):
    monkeypatch.delenv("CKO_FAULT_POISON_MARKER", raising=False)
    assert faults.poison_marker() is None
    monkeypatch.setenv("CKO_FAULT_POISON_MARKER", MARKER)
    assert faults.poison_marker() == b"POISON-X"


def test_device_hang_one_shot(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_DEVICE_HANG_S", "")
    faults.injected_device_hang_s()  # normalize module arm state
    monkeypatch.setenv("CKO_FAULT_DEVICE_HANG_S", "1.5")
    assert faults.injected_device_hang_s() == 1.5
    assert faults.injected_device_hang_s() == 0.0  # one-shot: fired
    monkeypatch.setenv("CKO_FAULT_DEVICE_HANG_S", "2.0")
    assert faults.injected_device_hang_s() == 2.0  # value change re-arms
    assert faults.injected_device_hang_s() == 0.0


def test_prepare_raises_on_poison_marker(monkeypatch):
    engine = WafEngine(BASE + EVIL_MONKEY)
    monkeypatch.setenv("CKO_FAULT_POISON_MARKER", MARKER)
    with pytest.raises(faults.DeviceFault):
        engine.prepare([_poison()])
    # Clean requests are untouched by the armed marker.
    v = engine.evaluate([HttpRequest(uri="/?pet=evilmonkey")])
    assert v[0].interrupted and v[0].status == 403


# -- fingerprints and registry ------------------------------------------------


def test_fingerprint_normalization():
    a = HttpRequest(
        method="post",
        uri="/x?q=1",
        headers=[("X-A", "1"), ("Content-Type", "t")],
        body=b"payload",
        remote_addr="10.0.0.1",
    )
    b = HttpRequest(
        method="POST",
        uri="/x?q=1",
        headers=[("content-type", "t"), ("x-a", "1")],  # order + case
        body=b"payload",
        remote_addr="10.9.9.9",  # source IP excluded
    )
    assert fingerprint(a) == fingerprint(b)
    c = HttpRequest(method="POST", uri="/x?q=1", body=b"payload2")
    assert fingerprint(a) != fingerprint(c)


def test_registry_eviction_ttl_flush():
    reg = QuarantineRegistry(max_entries=2, ttl_s=60.0)
    reg.add("fp1")
    reg.add("fp2")
    reg.add("fp3")  # oldest (fp1) evicted
    assert len(reg) == 2
    p = _poison()
    reg.add(fingerprint(p))  # fp2 evicted
    assert reg.match(p)
    assert reg.hits_total == 1
    assert reg.match(HttpRequest(uri="/clean")) is False
    assert reg.flush() == 2
    assert len(reg) == 0 and not reg.match(p)
    ttl = QuarantineRegistry(max_entries=8, ttl_s=0.05)
    ttl.add(fingerprint(p))
    assert ttl.match(p)
    time.sleep(0.08)
    assert not ttl.match(p)
    assert len(ttl) == 0


# -- bisector ------------------------------------------------------------------


class _PoisonOnlyEngine:
    """Stub engine that faults whenever a batch contains b'BAD'."""

    warmed = True

    def __init__(self):
        self.batches = []

    def evaluate(self, reqs):
        self.batches.append(len(reqs))
        if any(b"BAD" in r.body for r in reqs):
            raise RuntimeError("injected poison fault")
        return ["ok"] * len(reqs)


def test_bisector_isolates_offender():
    reg = QuarantineRegistry()
    forgiven = threading.Event()
    bis = PoisonBisector(reg, on_isolated=forgiven.set)
    bis.start()
    try:
        poison = HttpRequest(method="POST", uri="/p", body=b"x=BAD")
        reqs = [
            HttpRequest(uri="/a"),
            poison,
            HttpRequest(uri="/b"),
            HttpRequest(uri="/c"),
        ]
        assert bis.submit(_PoisonOnlyEngine(), RuntimeError("window fault"), reqs)
        assert _wait(lambda: len(reg) == 1, 10)
        assert reg.match(HttpRequest(method="POST", uri="/p", body=b"x=BAD"))
        assert not reg.match(HttpRequest(uri="/a"))
        assert reg.isolated_total == 1
        assert forgiven.wait(5)
    finally:
        bis.stop()


def test_bisector_sick_device_escalates_without_quarantine():
    """Every sub-dispatch fails AND the canary fails: that is a sick
    device, not poison — nothing is quarantined and the original error
    is escalated (the provisional breaker failure stands)."""

    class _SickEngine:
        warmed = True

        def evaluate(self, reqs):
            raise RuntimeError("device is sick")

    reg = QuarantineRegistry()
    escalated = []
    bis = PoisonBisector(reg, on_unisolated=escalated.append)
    bis.start()
    try:
        original = RuntimeError("window fault")
        reqs = [HttpRequest(uri="/a"), HttpRequest(uri="/b")]
        assert bis.submit(_SickEngine(), original, reqs)
        assert _wait(lambda: escalated, 10)
        assert escalated[0] is original
        assert len(reg) == 0 and reg.isolated_total == 0
    finally:
        bis.stop()


def test_bisector_singleton_window_uses_canary_control():
    """A one-request window has no clean sibling to prove the device;
    the canary control dispatch arbitrates and the offender is still
    quarantined."""
    reg = QuarantineRegistry()
    bis = PoisonBisector(reg)
    bis.start()
    try:
        poison = HttpRequest(method="POST", uri="/p", body=b"x=BAD")
        assert bis.submit(_PoisonOnlyEngine(), RuntimeError("boom"), [poison])
        assert _wait(lambda: len(reg) == 1, 10)
        assert reg.match(poison)
    finally:
        bis.stop()


# -- dispatch watchdog (raw batcher) ------------------------------------------


class _BlockingEngine:
    """Two-stage stub whose collect can be made to block until released."""

    def __init__(self, warmed=True, collect_error=None):
        self.warmed = warmed
        self.release = threading.Event()
        self.block_next = threading.Event()
        self.in_collect = threading.Event()
        self.collect_error = collect_error
        self.collected = 0

    def prepare(self, reqs):
        return list(reqs)

    def collect(self, inflight):
        self.in_collect.set()
        if self.block_next.is_set():
            self.block_next.clear()
            self.release.wait(timeout=30)
            if self.collect_error is not None:
                raise self.collect_error
        self.collected += 1
        return [("ok", r.uri) for r in inflight]

    def evaluate(self, reqs):
        return self.collect(self.prepare(reqs))


def test_watchdog_abandons_blown_window_collector_keeps_serving():
    eng = _BlockingEngine()
    b = MicroBatcher(lambda: eng, max_batch_size=1, max_batch_delay_ms=0)
    b.window_deadline_s = 0.3
    b.start()
    try:
        eng.block_next.set()
        t0 = time.monotonic()
        with pytest.raises(WindowAbandoned):
            b.evaluate(HttpRequest(uri="/hang"), timeout_s=10)
        # Abandoned promptly — not after the full readback wait.
        assert time.monotonic() - t0 < 5.0
        assert b.windows_abandoned == 1
        assert b.parked_readbacks == 1
        # The collector FIFO keeps moving: the next window still serves.
        v = b.evaluate(HttpRequest(uri="/ok"), timeout_s=10)
        assert v == ("ok", "/ok")
        # The parked readback un-parks itself when the stuck collect
        # finally returns.
        eng.release.set()
        assert _wait(lambda: b.parked_readbacks == 0, 10)
        assert b.windows_abandoned == 1
    finally:
        eng.release.set()
        b.stop()


@pytest.mark.parametrize(
    "device_done, abandoned",
    [(True, 0), (False, 1), (RuntimeError("cannot tell"), 1)],
    ids=["device-done-host-late", "device-not-done", "probe-raises"],
)
def test_watchdog_deadline_is_the_devices(device_done, abandoned):
    """A window past its deadline whose device work is done
    (``engine.device_done``) is the HOST's lateness: it is waited for,
    answered from its own collect and counted ``windows_host_late``, not
    abandoned. A device that is not done, or an engine that cannot
    tell, is abandoned as ever."""
    eng = _BlockingEngine()

    def probe(inflight):
        if isinstance(device_done, Exception):
            raise device_done
        return device_done

    eng.device_done = probe
    b = MicroBatcher(lambda: eng, max_batch_size=1, max_batch_delay_ms=0)
    b.window_deadline_s = 0.2
    b.request_timeout_s = 10.0
    b.start()
    try:
        eng.block_next.set()
        threading.Timer(0.8, eng.release.set).start()  # four deadlines late
        if abandoned:
            with pytest.raises(WindowAbandoned):
                b.evaluate(HttpRequest(uri="/late"), timeout_s=10)
        else:
            assert b.evaluate(HttpRequest(uri="/late"), timeout_s=10) == ("ok", "/late")
        assert b.windows_abandoned == abandoned
        assert b.windows_host_late == 1 - abandoned
        assert b.evaluate(HttpRequest(uri="/ok"), timeout_s=10) == ("ok", "/ok")
    finally:
        eng.release.set()
        b.stop()


def test_watchdog_host_late_wait_is_bounded_by_the_requests_budget():
    """With the device done and the host never finishing, the window is
    abandoned at half its requests' budget: the fallback keeps the rest."""
    eng = _BlockingEngine()
    eng.device_done = lambda inflight: True
    b = MicroBatcher(lambda: eng, max_batch_size=1, max_batch_delay_ms=0)
    b.window_deadline_s = 0.1
    b.request_timeout_s = 1.0
    b.start()
    try:
        eng.block_next.set()
        t0 = time.monotonic()
        with pytest.raises(WindowAbandoned):
            b.evaluate(HttpRequest(uri="/stuck"), timeout_s=10)
        assert 0.4 < time.monotonic() - t0 < 3.0
        assert (b.windows_host_late, b.windows_abandoned) == (1, 1)
    finally:
        eng.release.set()
        b.stop()


def test_engine_device_done_reads_the_outputs():
    """``WafEngine.device_done``: true once the window's outputs are
    computed, false while an injected hang stands for a hung device."""
    engine = WafEngine(BASE + EVIL_MONKEY)
    inflight = engine.prepare([HttpRequest(uri="/?q=1")])
    assert _wait(lambda: engine.device_done(inflight), 60)
    inflight.hung = True
    assert not engine.device_done(inflight)
    inflight.hung = False
    assert engine.collect(inflight)[0].status == 200
    assert engine.device_done(inflight)


def test_watchdog_disarmed_until_warmed():
    eng = _BlockingEngine(warmed=False)
    b = MicroBatcher(lambda: eng, max_batch_size=1, max_batch_delay_ms=0)
    b.window_deadline_s = 0.05
    assert b._window_deadline_for(eng) is None  # cold: never abandon
    eng.warmed = True
    assert b._window_deadline_for(eng) == 0.05
    b.window_deadline_s = 0  # explicit <= 0 disables
    assert b._window_deadline_for(eng) is None
    b.window_deadline_s = None  # auto: needs enough latency samples
    assert b._window_deadline_for(eng) is None
    for _ in range(b._deadline_min_samples):
        b.stats.record(1, 0.01)
    d = b._window_deadline_for(eng)
    assert d is not None and d >= 1.0  # 10x p99, floored at 1s


def test_late_loss_class_error_reaches_fault_hook_without_requests():
    """Regression: a DEVICE_LOST landing AFTER abandonment must still be
    classified (loss check only — requests_fn is None so the breaker is
    not double-fed)."""
    loss = faults.DeviceLostFault("DEVICE_LOST: device halted")
    eng = _BlockingEngine(collect_error=loss)
    b = MicroBatcher(lambda: eng, max_batch_size=1, max_batch_delay_ms=0)
    b.window_deadline_s = 0.3
    calls = []
    b.on_window_fault = lambda engine, err, requests_fn: calls.append(
        (err, requests_fn)
    )
    b.start()
    try:
        eng.block_next.set()
        with pytest.raises(WindowAbandoned):
            b.evaluate(HttpRequest(uri="/hang"), timeout_s=10)
        # The abandonment itself was classified with the window's requests.
        assert len(calls) == 1
        assert isinstance(calls[0][0], WindowAbandoned)
        assert calls[0][1] is not None
        eng.release.set()
        assert _wait(lambda: len(calls) == 2, 10)
        assert calls[1][0] is loss
        assert calls[1][1] is None
        assert _wait(lambda: b.parked_readbacks == 0, 10)
    finally:
        eng.release.set()
        b.stop()


def test_collector_wedged_flag_on_stop():
    eng = _BlockingEngine(warmed=False)  # watchdog off: collect runs inline
    b = MicroBatcher(lambda: eng, max_batch_size=1, max_batch_delay_ms=0)
    b._collector_join_s = 0.2
    b.start()
    try:
        eng.block_next.set()
        fut = b.submit(HttpRequest(uri="/hang"))
        assert _wait(lambda: eng.in_collect.is_set(), 10)
        b.stop()
        assert b.collector_wedged
        eng.release.set()
        assert fut.result(timeout=10) == ("ok", "/hang")
    finally:
        eng.release.set()


# -- sidecar-level: quarantine end to end -------------------------------------


def test_poison_isolated_and_routed_to_fallback(monkeypatch):
    """The tentpole invariant: one poison request faults its window,
    gets a real fallback verdict, is isolated and quarantined; repeats
    are answered off-device at batch-assembly time; the breaker never
    opens and the device path stays promoted."""
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE + EVIL_MONKEY)
    sc = _sidecar(engine)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        monkeypatch.setenv("CKO_FAULT_POISON_MARKER", MARKER)
        # Poison that also matches rule 3001: the fallback must still
        # produce the RIGHT verdict, not just any verdict.
        status, headers, _ = _http(
            sc.port,
            "/?pet=evilmonkey",
            method="POST",
            body=b"a=POISON-X",
        )
        assert status == 403
        assert headers["x-waf-rule-id"] == "3001"
        assert _wait(
            lambda: sc.stats()["quarantine"]["isolated_total"] >= 1, 30
        )
        assert sc.degraded.breaker.state == BREAKER_CLOSED
        assert sc.serving_mode() == "promoted"
        errs_before = sc.batcher.stats.errors
        # The same poison again: quarantined at assembly — no window
        # fault, same correct verdict.
        status, headers, _ = _http(
            sc.port,
            "/?pet=evilmonkey",
            method="POST",
            body=b"a=POISON-X",
        )
        assert status == 403
        assert headers["x-waf-rule-id"] == "3001"
        assert sc.batcher.stats.errors == errs_before
        assert sc.stats()["quarantine"]["hits_total"] >= 1
        # Clean traffic rides the device path, bit-identical verdicts.
        status, _, _ = _http(sc.port, "/?q=hello")
        assert status == 200
        assert sc.serving_mode() == "promoted"
        assert sc.degraded.breaker.state == BREAKER_CLOSED
        # Prometheus surface.
        _, _, metrics = _http(sc.port, "/waf/v1/metrics")
        assert b"cko_quarantine_isolated_total" in metrics
        assert b"cko_windows_abandoned_total" in metrics
        # Operator escape hatch: flush drops the entries.
        status, _, body = _http(
            sc.port, "/waf/v1/quarantine/flush", method="POST", body=b""
        )
        assert status == 200
        import json

        out = json.loads(body)
        assert out["flushed"] >= 1 and out["entries"] == 0
        assert sc.stats()["quarantine"]["entries"] == 0
    finally:
        sc.stop()


def test_isolation_invariant_neighbors_keep_their_verdicts(monkeypatch):
    """A faulted request never changes a neighbor's verdict: requests
    sharing the poison's window still get the exact ruleset verdicts
    (via the server's rescue path round 1, on-device round 2)."""
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE + EVIL_MONKEY)
    sc = _sidecar(engine, max_batch_size=16, max_batch_delay_ms=40.0)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        monkeypatch.setenv("CKO_FAULT_POISON_MARKER", MARKER)

        def _round():
            results = [None] * 8

            def one(i):
                if i == 3:
                    results[i] = _http(
                        sc.port,
                        "/?pet=evilmonkey&poison=1",
                        method="POST",
                        body=b"a=POISON-X",
                    )
                elif i % 2 == 0:
                    results[i] = _http(sc.port, f"/?pet=evilmonkey&i={i}")
                else:
                    results[i] = _http(sc.port, f"/?q=ok&i={i}")
            threads = [
                threading.Thread(target=one, args=(i,)) for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return results

        for round_no in (1, 2):
            results = _round()
            for i, (status, headers, _) in enumerate(results):
                if i == 3 or i % 2 == 0:
                    assert status == 403, (round_no, i, status)
                    assert headers["x-waf-rule-id"] == "3001"
                else:
                    assert status == 200, (round_no, i, status)
            if round_no == 1:
                assert _wait(
                    lambda: sc.stats()["quarantine"]["isolated_total"] >= 1,
                    30,
                )
        # Round 2's poison was assembly-routed, never a window fault.
        assert sc.stats()["quarantine"]["hits_total"] >= 1
        assert sc.degraded.breaker.state == BREAKER_CLOSED
        assert sc.serving_mode() == "promoted"
    finally:
        sc.stop()


def test_window_fault_taxonomy_routing(monkeypatch):
    """Loss-class errors go to the DeviceLossManager (breaker untouched,
    bisector not fed); generic errors feed the breaker AND the bisector."""
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    engine = WafEngine(BASE)
    sc = _sidecar(engine)
    sc.start()
    try:
        # Let the promotion probe finish first: its record_device_success
        # would reset the breaker count under the asserts below.
        assert _wait(lambda: sc.serving_mode() == "promoted")
        time.sleep(0.2)
        loss = faults.DeviceLostFault("DEVICE_LOST: backend gone")
        sc._on_window_fault(engine, loss, lambda: [HttpRequest(uri="/x")])
        dl = sc.degraded.device_loss
        assert dl is not None and dl.losses_total >= 1
        assert sc.degraded.breaker.snapshot()["consecutive_failures"] == 0
        assert sc.bisector.jobs_total == 0
        generic = RuntimeError("boom")
        sc._on_window_fault(engine, generic, lambda: [HttpRequest(uri="/x")])
        assert sc.degraded.breaker.snapshot()["consecutive_failures"] >= 1
        assert _wait(lambda: sc.bisector.jobs_total == 1, 10)
    finally:
        sc.stop()


def test_sidecar_watchdog_abandon_recovers(monkeypatch):
    """A one-shot device hang blows the window deadline: the request is
    re-answered from host fallback (real verdict), the readback parks
    and later un-parks, and serving stays promoted."""
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    monkeypatch.setenv("CKO_FAULT_DEVICE_HANG_S", "")
    faults.injected_device_hang_s()  # normalize one-shot arm state
    engine = WafEngine(BASE + EVIL_MONKEY)
    sc = _sidecar(engine, window_deadline_s=0.5)
    sc.start()
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted")
        assert sc.stats()["watchdog"]["effective_deadline_s"] == 0.5
        monkeypatch.setenv("CKO_FAULT_DEVICE_HANG_S", "2.0")
        t0 = time.monotonic()
        status, headers, _ = _http(sc.port, "/?pet=evilmonkey")
        took = time.monotonic() - t0
        assert status == 403
        assert headers["x-waf-rule-id"] == "3001"
        assert took < 2.0, took  # answered at the deadline, not the hang
        assert sc.batcher.windows_abandoned >= 1
        assert _wait(lambda: sc.batcher.parked_readbacks == 0, 15)
        status, _, _ = _http(sc.port, "/?q=hello")
        assert status == 200
        assert sc.serving_mode() == "promoted"
        assert sc.degraded.breaker.state == BREAKER_CLOSED
        st = sc.stats()["watchdog"]
        assert st["windows_abandoned"] >= 1 and st["collector_wedged"] is False
        assert st["windows_host_late"] == 0  # an injected hang is the device's
    finally:
        sc.stop()


# -- config plumbing ----------------------------------------------------------


def test_request_timeout_env_resolution(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    monkeypatch.setenv("CKO_REQUEST_TIMEOUT_S", "7.5")
    monkeypatch.setenv("CKO_WINDOW_DEADLINE_S", "2.25")
    engine = WafEngine(BASE)
    sc = _sidecar(engine)
    sc.start()
    try:
        assert sc.config.request_timeout_s == 7.5
        assert sc.batcher.request_timeout_s == 7.5
        assert sc.config.window_deadline_s == 2.25
        assert sc.batcher.window_deadline_s == 2.25
        assert sc.stats()["request_timeout_s"] == 7.5
    finally:
        sc.stop()


def test_request_timeout_config_beats_env(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    monkeypatch.setenv("CKO_REQUEST_TIMEOUT_S", "7.5")
    engine = WafEngine(BASE)
    sc = _sidecar(engine, request_timeout_s=5.0)
    sc.start()
    try:
        assert sc.config.request_timeout_s == 5.0
        assert sc.batcher.request_timeout_s == 5.0
    finally:
        sc.stop()


def test_cli_flags_resolve_to_config(monkeypatch):
    from coraza_kubernetes_operator_tpu.cmd.tpu_engine import build_config

    monkeypatch.delenv("CKO_REQUEST_TIMEOUT_S", raising=False)
    cfg = build_config(
        [
            "--cache-server-instance",
            "default/ruleset",
            "--request-timeout-seconds",
            "12",
            "--window-deadline-seconds",
            "3.5",
        ]
    )
    assert cfg.request_timeout_s == 12.0
    assert cfg.window_deadline_s == 3.5
    cfg = build_config(["--cache-server-instance", "default/ruleset"])
    assert cfg.request_timeout_s is None  # resolved at sidecar construction
    assert cfg.window_deadline_s is None
