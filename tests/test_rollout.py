"""Staged ruleset rollout (ISSUE 6): budgeted background compile,
shadow-traffic verification, automatic rollback.

Covers the acceptance criteria:

- with ``CKO_FAULT_COMPILE_STALL_S=30`` and a small compile budget, a
  reload neither stalls polling nor perturbs serving — the old engine
  keeps answering and the rollout is recorded as *failed*;
- with ``CKO_FAULT_SHADOW_DIVERGE_RATE`` set, a staged candidate
  auto-rolls back to last-known-good with zero dropped or misordered
  in-flight requests;
- clean candidates promote after N shadow windows, pushing the previous
  engine onto the last-known-good ring; ``POST /waf/v1/rollback``
  force-rolls serving back (409 on an empty ring);
- candidate device faults and latency regressions roll back without
  touching the serving breaker;
- the RuleSet controller mirrors rollout state onto a ``RolloutState``
  condition;
- ``/waf/v1/readyz`` reports not-ready while broken or unloaded
  (liveness stays on ``/waf/v1/healthz``).

The state-machine tests run against stub engines (no XLA) so the suite
stays fast; the sidecar-level tests compile the tiny test ruleset once
via the shared executable cache.
"""

import json
import threading
import time
import urllib.error
import urllib.request

from coraza_kubernetes_operator_tpu.cache import RuleSetCache, RuleSetCacheServer
from coraza_kubernetes_operator_tpu.engine.waf import Verdict
from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar
from coraza_kubernetes_operator_tpu.sidecar.rollout import (
    ROLLOUT_CODES,
    EngineRing,
    RolloutConfig,
    RolloutManager,
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
EVIL_TIGER = (
    'SecRule ARGS|REQUEST_URI "@contains eviltiger" '
    '"id:3002,phase:2,deny,status:403"\n'
)
EVIL_PANDA = (
    'SecRule ARGS|REQUEST_URI "@contains evilpanda" '
    '"id:3003,phase:2,deny,status:403"\n'
)
KEY = "default/ruleset"


def _http(port, path, method="GET", body=None, headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method, data=body,
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


# -- stub-engine state-machine tests (no XLA) ---------------------------------


ALLOW = Verdict(interrupted=False, status=200, rule_id=None)
DENY = Verdict(interrupted=True, status=403, rule_id=123)


class StubEngine:
    def __init__(self, warmed=True, verdict=ALLOW, fail=False, collect_delay_s=0.0):
        self.warmed = warmed
        self.verdict = verdict
        self.fail = fail
        self.collect_delay_s = collect_delay_s
        self.prewarmed = 0

    def prewarm(self, requests=None):
        self.prewarmed += 1
        return {"compiled": False, "wall_s": 0.0}

    def prepare(self, requests):
        if self.fail:
            raise faults.DeviceFault("stub candidate fault")
        return list(requests)

    def collect(self, inflight):
        if self.collect_delay_s:
            time.sleep(self.collect_delay_s)
        return [self.verdict for _ in inflight]


def _outcomes():
    out = {"promote": [], "fail": []}
    return out, (lambda r: out["promote"].append(r)), (lambda r: out["fail"].append(r))


def _wait_terminal(r, timeout_s=15.0):
    assert _wait(lambda: r.terminal, timeout_s), r.snapshot()
    return r.state


def test_rollout_config_env(monkeypatch):
    monkeypatch.setenv("CKO_COMPILE_BUDGET_S", "42.5")
    monkeypatch.setenv("CKO_SHADOW_PROMOTE_WINDOWS", "7")
    monkeypatch.setenv("CKO_ROLLOUT_RING", "1")  # clamped to the minimum 2
    cfg = RolloutConfig()
    assert cfg.compile_budget_s == 42.5
    assert cfg.promote_windows == 7
    assert cfg.ring_depth == 2
    # Explicit args beat the env.
    assert RolloutConfig(compile_budget_s=5.0).compile_budget_s == 5.0
    assert set(ROLLOUT_CODES) == {
        "idle", "staged", "shadowing", "promoted", "rolled_back", "failed"
    }


def test_engine_ring_lkg_order():
    ring = EngineRing(2)
    a, b, c = object(), object(), object()
    ring.push("v1", a)
    ring.push("v2", b)
    ring.push("v3", c)  # depth 2: v1 evicted
    assert ring.uuids() == ["v2", "v3"]
    assert ring.pop() == ("v3", c)  # newest-first: the most recent LKG
    assert ring.pop() == ("v2", b)
    assert ring.pop() is None
    ring.push("vx", None)  # None engines are never ring-worthy
    assert len(ring) == 0


def test_manager_promotes_via_idle_self_check():
    out, on_promote, on_fail = _outcomes()
    mgr = RolloutManager(
        RolloutConfig(compile_budget_s=30, promote_windows=2, idle_check_s=0.05)
    )
    baseline = StubEngine()
    r = mgr.begin(
        "t/a", "v2", baseline,
        build=lambda: (StubEngine(), None),
        on_promote=on_promote, on_fail=on_fail,
    )
    assert _wait_terminal(r) == "promoted"
    assert out["promote"] and not out["fail"]
    assert r.engine.prewarmed == 1  # candidate AOT-prewarmed before shadowing
    assert r.shadow_windows >= 2
    assert mgr.promoted == 1
    assert mgr.state_for("t/a") == "promoted"
    assert mgr.state_for("t/unknown") == "idle"


def test_manager_budget_blown_records_failed_without_waiting(monkeypatch):
    out, on_promote, on_fail = _outcomes()
    mgr = RolloutManager(RolloutConfig(compile_budget_s=0.3, promote_windows=1))
    built = threading.Event()

    def slow_build():
        time.sleep(2.0)  # stands in for a minutes-long compile
        built.set()
        return (StubEngine(), None)

    t0 = time.monotonic()
    r = mgr.begin("t/a", "v2", StubEngine(), slow_build, on_promote, on_fail)
    assert _wait(lambda: r.terminal, 1.5)
    recorded_after = time.monotonic() - t0
    assert r.state == "failed" and "budget" in r.reason
    assert recorded_after < 1.5, recorded_after  # long before the build ends
    assert out["fail"] and not out["promote"]
    # The late build result is discarded, never promoted.
    assert built.wait(5)
    time.sleep(0.1)
    assert r.state == "failed"
    assert mgr.failed == 1 and mgr.promoted == 0


def test_manager_divergence_rolls_back_via_mirrored_windows():
    out, on_promote, on_fail = _outcomes()
    mgr = RolloutManager(
        RolloutConfig(compile_budget_s=30, promote_windows=50, idle_check_s=5.0)
    )
    baseline = StubEngine(verdict=ALLOW)
    r = mgr.begin(
        "t/a", "v2", baseline,
        build=lambda: (StubEngine(verdict=DENY), None),  # diverges on everything
        on_promote=on_promote, on_fail=on_fail,
    )
    assert _wait(lambda: r.state == "shadowing", 10), r.snapshot()
    for i in range(20):
        mgr.mirror_window(baseline, [f"req{i}"], [ALLOW], 0.001)
        if r.terminal:
            break
        time.sleep(0.05)
    assert _wait_terminal(r) == "rolled_back"
    assert "divergence" in r.reason
    assert out["fail"] and not out["promote"]
    assert mgr.rolled_back == 1
    assert mgr.shadow_totals()["diverged_requests"] >= 1


def test_manager_candidate_fault_rolls_back():
    out, on_promote, on_fail = _outcomes()
    mgr = RolloutManager(
        RolloutConfig(compile_budget_s=30, promote_windows=2, idle_check_s=5.0)
    )
    baseline = StubEngine()
    candidate = StubEngine()
    r = mgr.begin(
        "t/a", "v2", baseline,
        build=lambda: (candidate, None),
        on_promote=on_promote, on_fail=on_fail,
    )
    assert _wait(lambda: r.state == "shadowing", 10)
    candidate.fail = True  # faults only once live windows replay through it
    mgr.mirror_window(baseline, ["req"], [ALLOW], 0.001)
    assert _wait_terminal(r) == "rolled_back"
    assert "device fault" in r.reason
    assert out["fail"]


def test_manager_latency_regression_rolls_back():
    out, on_promote, on_fail = _outcomes()
    mgr = RolloutManager(
        RolloutConfig(
            compile_budget_s=30, promote_windows=2, idle_check_s=5.0,
            latency_ratio=2.0,
        )
    )
    baseline = StubEngine()
    r = mgr.begin(
        "t/a", "v2", baseline,
        # Candidate answers identically but 50ms/window vs ~0 serving.
        build=lambda: (StubEngine(collect_delay_s=0.05), None),
        on_promote=on_promote, on_fail=on_fail,
    )
    assert _wait(lambda: r.state == "shadowing", 10)
    for i in range(4):
        mgr.mirror_window(baseline, [f"req{i}"], [ALLOW], 0.001)
        if r.terminal:
            break
        time.sleep(0.08)
    assert _wait_terminal(r) == "rolled_back"
    assert "latency regression" in r.reason
    assert out["fail"]


def test_manager_abort_supersession():
    out, on_promote, on_fail = _outcomes()
    mgr = RolloutManager(
        RolloutConfig(compile_budget_s=30, promote_windows=50, idle_check_s=5.0)
    )
    r = mgr.begin(
        "t/a", "v2", StubEngine(),
        build=lambda: (StubEngine(), None),
        on_promote=on_promote, on_fail=on_fail,
    )
    assert _wait(lambda: r.state == "shadowing", 10)
    assert mgr.abort("t/a", "superseded by v3")
    assert r.state == "rolled_back" and "superseded" in r.reason
    assert mgr.active("t/a") is None
    # Outcome hooks are reserved for the rollout's own verdicts; an abort
    # is the caller's decision and must not double-count a failed reload.
    assert not out["fail"] and not out["promote"]


def test_manager_on_state_emits_transitions():
    states = []
    mgr = RolloutManager(
        RolloutConfig(compile_budget_s=30, promote_windows=1, idle_check_s=0.05),
        on_state=lambda key, state, msg: states.append((key, state)),
    )
    out, on_promote, on_fail = _outcomes()
    r = mgr.begin(
        "ns/rs", "v2", StubEngine(),
        build=lambda: (StubEngine(), None),
        on_promote=on_promote, on_fail=on_fail,
    )
    assert _wait_terminal(r) == "promoted"
    assert ("ns/rs", "staged") in states
    assert ("ns/rs", "shadowing") in states
    assert states[-1] == ("ns/rs", "promoted")


def test_shadow_queue_full_drops_and_counts():
    mgr = RolloutManager(
        RolloutConfig(
            compile_budget_s=30, promote_windows=500, idle_check_s=30.0,
            queue_depth=2,
        )
    )
    out, on_promote, on_fail = _outcomes()
    baseline = StubEngine()
    r = mgr.begin(
        "t/a", "v2", baseline,
        build=lambda: (StubEngine(collect_delay_s=0.2), None),  # slow drain
        on_promote=on_promote, on_fail=on_fail,
    )
    assert _wait(lambda: r.state == "shadowing", 10)
    for i in range(30):  # far faster than the candidate drains
        mgr.mirror_window(baseline, [f"req{i}"], [ALLOW], 0.0)
    assert mgr.shadow_totals()["dropped_windows"] > 0
    mgr.abort("t/a", "test over")


def test_injected_shadow_diverge_knob(monkeypatch):
    monkeypatch.delenv("CKO_FAULT_SHADOW_DIVERGE_RATE", raising=False)
    assert not faults.injected_shadow_diverge()
    monkeypatch.setenv("CKO_FAULT_SHADOW_DIVERGE_RATE", "1.0")
    assert faults.injected_shadow_diverge()
    monkeypatch.setenv("CKO_FAULT_SHADOW_DIVERGE_RATE", "0.5")
    monkeypatch.setenv("CKO_FAULT_SHADOW_DIVERGE_SEED", "3")
    draws = [faults.injected_shadow_diverge() for _ in range(64)]
    assert any(draws) and not all(draws)
    # Same seed ⇒ same stream (reseeding resets the generator).
    monkeypatch.setenv("CKO_FAULT_SHADOW_DIVERGE_SEED", "4")
    faults.injected_shadow_diverge()
    monkeypatch.setenv("CKO_FAULT_SHADOW_DIVERGE_SEED", "3")
    assert [faults.injected_shadow_diverge() for _ in range(64)] == draws


def _fake_engine(n_rules=1):
    from types import SimpleNamespace

    return SimpleNamespace(
        compiled=SimpleNamespace(n_rules=n_rules, n_groups=1), warmed=True
    )


def test_gate_refused_uuid_not_rollout_latched():
    """An analysis-gate refusal must stay re-admittable through
    CKO_ANALYZE_OVERRIDE=1: only the override-aware _rejected_uuid latch
    may hold it — the override-blind rollout latch is for budget blows,
    divergence, and faults."""
    from types import SimpleNamespace

    from coraza_kubernetes_operator_tpu.sidecar.reloader import RuleReloader

    r = RuleReloader("http://127.0.0.1:1", "t/a")
    r._rejected_uuid = "v2"
    r._rollout_failed(SimpleNamespace(uuid="v2"))  # the refusal's on_fail
    assert r.failed_reloads == 1
    assert not r._is_rollout_latched("v2")  # override path stays open
    r._rollout_failed(SimpleNamespace(uuid="v3"))  # e.g. a blown budget
    assert r._is_rollout_latched("v3")


def test_forced_rollback_cancels_pending_promotion_swap():
    """The promotion-vs-forced-rollback race: a candidate that won its
    terminal transition just before the operator's rollback must NOT
    swap in afterwards — the staging-time epoch is stale and the
    promotion is discarded (and its uuid latched)."""
    from types import SimpleNamespace

    from coraza_kubernetes_operator_tpu.sidecar.reloader import RuleReloader

    r = RuleReloader("http://127.0.0.1:1", "t/a")
    e1, e2, e3 = _fake_engine(), _fake_engine(), _fake_engine()
    r.seed(e1, "v1")
    r._swap("v2", e2, None)  # a normal promotion: ring now holds v1
    epoch = r._swap_epoch  # what a candidate staged NOW would capture
    out = r.force_rollback()
    assert out["rolled_back_to"] == "v1" and r.engine is e1
    # The raced promotion arrives with the pre-rollback epoch: discarded.
    r._rollout_promoted(SimpleNamespace(uuid="v3", engine=e3, analysis=None), epoch)
    assert r.engine is e1 and r.current_uuid == "v1"
    assert r._is_rollout_latched("v3")
    assert r.reloads == 1  # only the v2 swap ever counted
    # A candidate staged AFTER the rollback promotes normally.
    r._rollout_promoted(
        SimpleNamespace(uuid="v4", engine=e3, analysis=None), r._swap_epoch
    )
    assert r.engine is e3 and r.current_uuid == "v4"


# -- sidecar integration (real engines, CPU backend) --------------------------


def _stack(cache_rules: str, **cfg):
    cache = RuleSetCache()
    cache.put(KEY, cache_rules)
    srv = RuleSetCacheServer(cache, host="127.0.0.1", port=0)
    srv.start()
    sc = TpuEngineSidecar(
        SidecarConfig(
            host="127.0.0.1",
            port=0,
            cache_base_url=f"http://127.0.0.1:{srv.port}",
            instance_key=KEY,
            poll_interval_s=0.05,
            **cfg,
        )
    )
    sc.start()
    return cache, srv, sc


def test_compile_stall_reload_never_stalls_polls_or_serving(monkeypatch):
    """ISSUE 6 acceptance: CKO_FAULT_COMPILE_STALL_S=30 + a 1.5s budget —
    the reload is recorded as a FAILED rollout within seconds, the old
    engine answers throughout, and the poll loop keeps sweeping."""
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    cache, srv, sc = _stack(
        BASE + EVIL_MONKEY,
        compile_budget_s=1.5,
        shadow_promote_windows=2,
        shadow_idle_check_s=0.2,
    )
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted", 120)
        engine_before = sc.tenants.engine_for(None)
        # The stall hits the candidate's canary dispatch (unwarmed
        # engine), exactly like a real minutes-long first XLA compile.
        monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "30")
        polls_before = sc.reloader.polls
        t0 = time.monotonic()
        cache.put(KEY, BASE + EVIL_MONKEY + EVIL_TIGER)
        assert _wait(lambda: sc.rollout.failed >= 1, 30), sc.rollout.stats()
        assert time.monotonic() - t0 < 10.0  # recorded, not waited out
        # Serving never flinched: same engine object, verdicts flow fast.
        assert sc.tenants.engine_for(None) is engine_before
        t1 = time.monotonic()
        status, _, _ = _http(sc.port, "/?pet=evilmonkey")
        assert status == 403
        assert time.monotonic() - t1 < 5.0
        assert sc.serving_mode() == "promoted"
        # Polling kept sweeping while the abandoned candidate sleeps.
        assert _wait(lambda: sc.reloader.polls > polls_before + 3, 10)
        stats = sc.stats()
        assert stats["rollout"]["failed"] == 1
        snap = stats["rollout"]["rollouts"][KEY]
        assert snap["state"] == "failed" and "budget" in snap["reason"]
        assert stats["reloads"] == 1  # the boot load only: no swap happened
    finally:
        sc.stop()
        srv.stop()


def test_shadow_divergence_auto_rollback_zero_dropped_requests(monkeypatch):
    """ISSUE 6 acceptance: with CKO_FAULT_SHADOW_DIVERGE_RATE set, a
    staged candidate auto-rolls back to last-known-good while in-flight
    traffic sees zero dropped or misordered verdicts."""
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    cache, srv, sc = _stack(
        BASE + EVIL_MONKEY,
        shadow_promote_windows=100,  # divergence must decide, not promotion
        shadow_sample_rate=1.0,
        shadow_idle_check_s=0.3,
    )
    stop = threading.Event()
    bad: list = []

    def storm():
        i = 0
        while not stop.is_set():
            attack = i % 2 == 0
            path = f"/?pet=evilmonkey&i={i}" if attack else f"/?q=fine&i={i}"
            try:
                status, _, body = _http(sc.port, path)
            except Exception as err:
                bad.append((path, repr(err)))
                i += 1
                continue
            if status != (403 if attack else 200) or not body:
                bad.append((path, status))
            i += 1

    try:
        assert _wait(lambda: sc.serving_mode() == "promoted", 120)
        engine_before = sc.tenants.engine_for(None)
        uuid_before = sc.reloader.current_uuid
        monkeypatch.setenv("CKO_FAULT_SHADOW_DIVERGE_RATE", "1.0")
        threads = [threading.Thread(target=storm, daemon=True) for _ in range(2)]
        for t in threads:
            t.start()
        cache.put(KEY, BASE + EVIL_MONKEY + EVIL_PANDA)
        assert _wait(lambda: sc.rollout.rolled_back >= 1, 60), sc.rollout.stats()
        # Ordered in-flight check DURING/after rollback: a bulk batch's
        # verdict array must line up with its request order.
        payload = json.dumps(
            {
                "requests": [
                    {"uri": f"/?i={i}" + ("&pet=evilmonkey" if i % 3 == 0 else "")}
                    for i in range(30)
                ]
            }
        ).encode()
        status, _, body = _http(sc.port, "/waf/v1/evaluate", method="POST", body=payload)
        assert status == 200, body
        verdicts = json.loads(body)["verdicts"]
        assert [v["interrupted"] for v in verdicts] == [
            i % 3 == 0 for i in range(30)
        ]
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not bad, bad[:5]
        # Rolled back to last-known-good: serving engine and uuid intact,
        # the diverging version never served a request.
        assert sc.tenants.engine_for(None) is engine_before
        assert sc.reloader.current_uuid == uuid_before
        status, _, _ = _http(sc.port, "/?pet=evilpanda")
        assert status == 200  # panda rule never went live
        snap = sc.stats()["rollout"]["rollouts"][KEY]
        assert snap["state"] == "rolled_back" and "divergence" in snap["reason"]
        assert sc.stats()["rollout"]["shadow"]["diverged_requests"] >= 1
    finally:
        stop.set()
        sc.stop()
        srv.stop()


def test_clean_rollout_promotes_then_forced_rollback_endpoint(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    monkeypatch.delenv("CKO_FAULT_SHADOW_DIVERGE_RATE", raising=False)
    cache, srv, sc = _stack(
        BASE + EVIL_MONKEY,
        shadow_promote_windows=2,
        shadow_idle_check_s=0.2,
    )
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted", 120)
        v1_engine = sc.tenants.engine_for(None)
        v1_uuid = sc.reloader.current_uuid
        # v2 adds a rule the (idle) shadow traffic never triggers: clean.
        cache.put(KEY, BASE + EVIL_MONKEY + EVIL_TIGER)
        assert _wait(lambda: sc.tenants.total_reloads >= 2, 60), sc.rollout.stats()
        assert sc.reloader.current_uuid != v1_uuid
        assert _http(sc.port, "/?pet=eviltiger")[0] == 403
        # Promotion pushed v1 onto the last-known-good ring…
        assert sc.stats()["tenants"][KEY]["lkg_ring"] == [v1_uuid]
        snap = sc.stats()["rollout"]["rollouts"][KEY]
        assert snap["state"] == "promoted"
        assert snap["shadow_windows"] >= 2
        # …and the rollout candidate came pre-warmed: promoted mode held
        # (no fallback dip) right through the swap.
        assert sc.serving_mode() == "promoted"

        # Forced rollback: back to v1 — tiger allowed again, monkey still
        # denied, the bad uuid latched (no immediate re-stage).
        status, _, body = _http(sc.port, "/waf/v1/rollback", method="POST", body=b"")
        assert status == 200, body
        out = json.loads(body)
        assert out["rolled_back_to"] == v1_uuid
        assert sc.tenants.engine_for(None) is v1_engine
        assert _http(sc.port, "/?pet=eviltiger")[0] == 200
        assert _http(sc.port, "/?pet=evilmonkey")[0] == 403
        assert sc.stats()["rollbacks_forced"] == 1
        time.sleep(0.3)  # a few poll sweeps: the latched uuid must not return
        assert sc.tenants.engine_for(None) is v1_engine
        # Ring drained: a second rollback has nothing to return to.
        status, _, body = _http(sc.port, "/waf/v1/rollback", method="POST", body=b"")
        assert status == 409, body
        _, _, metrics = _http(sc.port, "/waf/v1/metrics")
        assert b"cko_rollback_forced_total 1" in metrics
        assert b'cko_rollouts_total{outcome="promoted"} 1' in metrics
    finally:
        sc.stop()
        srv.stop()


def test_rollback_endpoint_409_without_history():
    cache, srv, sc = _stack(BASE + EVIL_MONKEY)
    try:
        assert _wait(sc.ready, 60)
        status, _, body = _http(sc.port, "/waf/v1/rollback", method="POST", body=b"")
        assert status == 409
        assert b"ring empty" in body
        status, _, _ = _http(
            sc.port, "/waf/v1/rollback", method="POST", body=b"not json"
        )
        assert status == 400
    finally:
        sc.stop()
        srv.stop()


def test_rollout_disabled_reverts_to_inline_reloads():
    cache, srv, sc = _stack(BASE + EVIL_MONKEY, rollout_enabled=False)
    try:
        assert _wait(sc.ready, 60)
        assert sc.rollout is None
        assert sc.batcher.on_window is None
        cache.put(KEY, BASE + EVIL_MONKEY + EVIL_TIGER)
        assert _wait(lambda: sc.tenants.total_reloads >= 2, 30)
        assert sc.stats()["rollout"] == {"enabled": False}
    finally:
        sc.stop()
        srv.stop()


def test_readyz_tracks_broken_mode(monkeypatch):
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    cache, srv, sc = _stack(BASE + EVIL_MONKEY, breaker_cooldown_s=300.0)
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted", 120)
        status, _, body = _http(sc.port, "/waf/v1/readyz")
        assert status == 200 and b"promoted" in body
        # healthz stays liveness-green whatever the serving mode.
        assert _http(sc.port, "/waf/v1/healthz")[0] == 200
        for _ in range(sc.config.breaker_threshold):
            sc.degraded.breaker.record_failure()
        assert sc.serving_mode() == "broken"
        status, _, body = _http(sc.port, "/waf/v1/readyz")
        assert status == 503 and b"broken" in body
        assert _http(sc.port, "/waf/v1/healthz")[0] == 200
        sc.degraded.breaker.record_success()
        assert _http(sc.port, "/waf/v1/readyz")[0] == 200
    finally:
        sc.stop()
        srv.stop()


# -- control plane: RolloutState condition ------------------------------------


def test_controller_mirrors_rollout_state_condition():
    from coraza_kubernetes_operator_tpu.controlplane import (
        ConfigMap,
        FakeRecorder,
        ObjectMeta,
        ObjectStore,
        RuleSet,
        RuleSetSpec,
        RuleSourceReference,
    )
    from coraza_kubernetes_operator_tpu.controlplane.conditions import get_condition
    from coraza_kubernetes_operator_tpu.controlplane.ruleset_controller import (
        RuleSetReconciler,
    )

    store = ObjectStore()
    cache = RuleSetCache()
    recorder = FakeRecorder()
    store.create(
        ConfigMap(
            metadata=ObjectMeta(name="cm", namespace="ns"),
            data={"rules": EVIL_MONKEY},
        )
    )
    store.create(
        RuleSet(
            metadata=ObjectMeta(name="rs", namespace="ns"),
            spec=RuleSetSpec(rules=[RuleSourceReference("cm")]),
        )
    )
    rec = RuleSetReconciler(store, cache, recorder)
    rec.reconcile("ns", "rs")

    # The sidecar's RolloutManager drives this via its on_state callback.
    mgr = RolloutManager(
        RolloutConfig(compile_budget_s=30, promote_windows=1, idle_check_s=0.05),
        on_state=lambda key, state, msg: rec.observe_rollout(key, state, msg),
    )
    out, on_promote, on_fail = _outcomes()
    r = mgr.begin(
        "ns/rs", "v2", StubEngine(),
        build=lambda: (StubEngine(), None),
        on_promote=on_promote, on_fail=on_fail,
    )
    assert _wait_terminal(r) == "promoted"
    assert _wait(
        lambda: (
            (c := get_condition(
                store.try_get("RuleSet", "ns", "rs").status.conditions,
                "RolloutState",
            )) is not None
            and c.reason == "RolloutPromoted"
        ),
        10,
    )
    cond = get_condition(
        store.try_get("RuleSet", "ns", "rs").status.conditions, "RolloutState"
    )
    assert cond.status == "True"
    assert recorder.has_event("Normal", "RolloutPromoted")

    # Rollback shows False + a Warning event, and unknown keys are ignored.
    rec.observe_rollout("ns/rs", "rolled_back", "verdict divergence 1.0")
    cond = get_condition(
        store.try_get("RuleSet", "ns", "rs").status.conditions, "RolloutState"
    )
    assert cond.status == "False" and cond.reason == "RolloutRolledBack"
    assert recorder.has_event("Warning", "RolloutRolledBack")
    rec.observe_rollout("ns/ghost", "promoted", "")  # must not raise


# -- satellites ----------------------------------------------------------------


def test_compile_inflight_counter_tracks_abandoned_compiles():
    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE

    assert EXEC_CACHE.inflight == 0
    assert "inflight" in EXEC_CACHE.stats()


def test_sidecar_shadow_mirrors_live_windows(monkeypatch):
    """End-to-end shadow accounting: live batcher windows (not just idle
    canaries) reach the candidate — the mirror hook, sampling, and the
    parity compare all ride the real prepare/collect split."""
    monkeypatch.setenv("CKO_FAULT_COMPILE_STALL_S", "0")
    cache, srv, sc = _stack(
        BASE + EVIL_MONKEY,
        shadow_promote_windows=3,
        shadow_sample_rate=1.0,
        shadow_idle_check_s=5.0,  # idle checks too slow to promote alone
    )
    stop = threading.Event()

    def traffic():
        i = 0
        while not stop.is_set():
            _http(sc.port, f"/?q=fine&i={i}")
            i += 1

    t = threading.Thread(target=traffic, daemon=True)
    try:
        assert _wait(lambda: sc.serving_mode() == "promoted", 120)
        t.start()
        cache.put(KEY, BASE + EVIL_MONKEY + EVIL_TIGER)
        assert _wait(lambda: sc.tenants.total_reloads >= 2, 60), sc.rollout.stats()
        assert sc.stats()["rollout"]["shadow"]["windows"] >= 3
        assert sc.stats()["rollout"]["shadow"]["diverged_requests"] == 0
    finally:
        stop.set()
        t.join(timeout=10)
        sc.stop()
        srv.stop()
