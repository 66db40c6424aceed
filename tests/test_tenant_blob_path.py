"""A trusted ``X-Waf-Tenant`` request rides the blob windows (PR 33).

Six namespaced RuleSets over four rule texts on one async-frontend
sidecar that trusts the tenant header: a request is sliced into the
window of its tenant's *(engine group, lane)*, judged by that group's
rule text and by no other, and answered in request order although one
socket read closes one window per resident engine. The reference of
every verdict here is the plain host evaluator on the tenant's own text.
"""

import socket
import time
from concurrent.futures import Future

import pytest

from coraza_kubernetes_operator_tpu.cache import RuleSetCache, RuleSetCacheServer
from coraza_kubernetes_operator_tpu.cmd.tpu_engine import build_config
from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
from coraza_kubernetes_operator_tpu.observability.stages import WindowStages
from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar
from coraza_kubernetes_operator_tpu.sidecar.batcher import (
    LANE_INTERACTIVE,
    MicroBatcher,
    _BlobWindow,
    _FairQueue,
)
from coraza_kubernetes_operator_tpu.sidecar.tenants import (
    EngineGroup,
    TenantGroups,
    TenantManager,
)

BASE = "SecRuleEngine On\nSecRequestBodyAccess On\n"


def _rule(rid: int, word: str) -> str:
    return (f'SecRule ARGS|REQUEST_BODY "@contains {word}" '
            f'"id:{rid},phase:2,deny,status:403,t:none"\n')


TEXTS = {
    "a": BASE + _rule(100, "alpha-attack"),
    "b": BASE + _rule(200, "beta-attack"),
    "c": BASE + _rule(100, "alpha-attack") + _rule(300, "gamma-attack"),
    "d": BASE + _rule(400, "delta-attack"),
}
# Tenant -> text, the default tenant first; two tenants share text a
# and two text b, so six tenants are four engine groups.
TENANTS = {"t0/rs": "a", "t1/rs": "b", "t2/rs": "c", "t3/rs": "d", "t4/rs": "a", "t5/rs": "b"}
WORDS = ["alpha-attack", "beta-attack", "gamma-attack", "delta-attack", "benign"]


def _wait(predicate, timeout_s=90.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return False


def _start(cache_port=None, engine=None, **kw) -> TpuEngineSidecar:
    if cache_port is not None:
        kw.update(cache_base_url=f"http://127.0.0.1:{cache_port}",
                  instance_key=",".join(TENANTS))
    sc = TpuEngineSidecar(
        SidecarConfig(host="127.0.0.1", port=0, poll_interval_s=0.1,
                      max_batch_delay_ms=kw.pop("max_batch_delay_ms", 20.0),
                      shadow_promote_windows=0, **kw),
        engine=engine,
    )
    sc.start()
    assert _wait(lambda: sc.serving_mode() == "promoted"), sc.serving_mode()
    return sc


@pytest.fixture(scope="module")
def stack():
    cache = RuleSetCache()
    for tenant, text in TENANTS.items():
        cache.put(tenant, TEXTS[text])
    srv = RuleSetCacheServer(cache, host="127.0.0.1", port=0)
    srv.start()
    sc = _start(srv.port, trust_tenant_header=True)
    assert _wait(lambda: all(t["loaded"] for t in sc.stats()["tenants"].values()))
    assert _wait(lambda: sc.serving_mode() == "promoted")
    yield cache, sc
    sc.stop()
    srv.stop()


def _wire(method, uri, tenant=None, body=b"", extra=()):
    head = [f"{method} {uri} HTTP/1.1", "Host: t"]
    if tenant is not None:
        head.append(f"X-Waf-Tenant: {tenant}")
    head += list(extra)
    if body:
        head += ["Content-Type: application/x-www-form-urlencoded",
                 f"Content-Length: {len(body)}"]
    return ("\r\n".join(head) + "\r\n\r\n").encode() + body


def _pipeline(port, payload: bytes, n: int):
    """One write down one connection; (status, rule id) of n replies."""
    out = []
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        s.sendall(payload)
        f = s.makefile("rb")
        for _ in range(n):
            status = int(f.readline().split()[1])
            headers = {}
            while (ln := f.readline()) not in (b"\r\n", b""):
                k, _, v = ln.decode("latin-1").partition(":")
                headers[k.strip().lower()] = v.strip()
            f.read(int(headers.get("content-length", 0)))
            out.append((status, headers.get("x-waf-rule-id"), headers.get("x-waf-action")))
    return out


def _send(sc, reqs):
    """(tenant, method, uri, body) requests pipelined in one write."""
    return _pipeline(sc.port, b"".join(_wire(m, u, t, b) for t, m, u, b in reqs), len(reqs))


_ENGINES: dict = {}


def _reference(tenant, method, uri, body=b"", extra_headers=()):
    """The plain host evaluator on the tenant's own rule text."""
    text = TEXTS[TENANTS[tenant]]
    engine = _ENGINES.get(text) or _ENGINES.setdefault(text, WafEngine(text))
    headers = [("Host", "t"), ("X-Waf-Tenant", tenant), *extra_headers]
    if body:
        headers += [("Content-Type", "application/x-www-form-urlencoded"),
                    ("Content-Length", str(len(body)))]
    v = engine.host_fallback.evaluate(
        [HttpRequest(method=method, uri=uri, headers=headers, body=body)])[0]
    return (v.status, str(v.rule_id)) if v.interrupted else (200, None)


def _frontend(sc):
    return dict(sc.stats()["frontend"])


def _burst(lane: str, n: int, salt: str):
    """n requests, tenants and attack words cycling out of step, so
    every tenant meets every word; all in one lane."""
    tenants = list(TENANTS)
    reqs = []
    for i in range(n):
        tenant, word = tenants[i % len(tenants)], WORDS[(i // 2) % len(WORDS)]
        if lane == "bulk":
            reqs.append((tenant, "POST", f"/submit?n={salt}{i}", f"v={word}&k={salt}{i}".encode()))
        else:
            reqs.append((tenant, "GET", f"/q?v={word}&k={salt}{i}", b""))
    return reqs


@pytest.mark.parametrize("lane", ["interactive", "bulk"])
def test_mixed_tenant_burst_agrees_with_each_tenants_host_evaluator(stack, lane):
    _cache, sc = stack
    reqs = _burst(lane, 36, f"mix{lane}")
    before = _frontend(sc)
    got = _send(sc, reqs)
    after = _frontend(sc)
    want = [_reference(t, m, u, b) for t, m, u, b in reqs]
    assert [(s, r) for s, r, _a in got] == want
    assert {s for s, _r in want} == {200, 403}  # the burst holds both
    # every one rode a blob window, none the per-request Python path
    assert after["python_path_requests_total"] == before["python_path_requests_total"]
    assert after["tenant_requests_total"] - before["tenant_requests_total"] == len(reqs)
    assert after["tenant_blob_requests_total"] - before["tenant_blob_requests_total"] == len(reqs)
    assert after["lane_windows"][lane] > before["lane_windows"][lane]
    other = "bulk" if lane == "interactive" else "interactive"
    assert after["lane_windows"][other] == before["lane_windows"][other]


def test_one_read_closes_one_window_per_resident_engine(stack):
    _cache, sc = stack
    reqs = _burst("interactive", 24, "perread")  # well under one segment
    before, groups_before = _frontend(sc), sc.stats()["tenant_groups"]
    _send(sc, reqs)
    after, groups_after = _frontend(sc), sc.stats()["tenant_groups"]
    reads = after["window_reads_total"] - before["window_reads_total"]
    windows = after["blob_windows_total"] - before["blob_windows_total"]
    assert (reads, windows) == (1, 4)
    assert groups_after["resident_engines"] == 4 and groups_after["trusted"]
    grew = {k: g["blob_windows"] - groups_before["groups"][k]["blob_windows"]
            for k, g in groups_after["groups"].items()}
    # a group is named by its first tenant; t4 and t5 ride t0's and t1's
    assert grew == {"t0/rs": 1, "t1/rs": 1, "t2/rs": 1, "t3/rs": 1}
    assert [g["tenants"] for g in groups_after["groups"].values()] == [2, 2, 1, 1]


@pytest.mark.parametrize("repeats", [1, 3])
def test_same_bytes_under_tenants_of_different_texts_differ(stack, repeats):
    """Also when repeated with the verdict cache on: a verdict cached
    for one rule text never answers a request of another."""
    _cache, sc = stack
    uri = f"/probe?v=gamma-attack&r={repeats}"
    for _ in range(repeats):
        got = _pipeline(sc.port, _wire("GET", uri, "t2/rs") + _wire("GET", uri, "t0/rs")
                        + _wire("GET", uri, "t3/rs"), 3)
        assert [(s, r) for s, r, _a in got] == [(403, "300"), (200, None), (200, None)]
    if repeats > 1:
        assert sc.stats()["verdict_cache"]["hits_total"] > 0  # each tenant's own repeats


def test_two_tenants_of_one_text_share_a_group_and_may_share_verdicts(stack):
    _cache, sc = stack
    groups = sc.tenants.groups
    assert groups.lookup(b"t0/rs") is groups.lookup(b"t4/rs") is groups.default
    assert groups.lookup(b"t1/rs") is groups.lookup(b"t5/rs")
    assert groups.lookup(b"t0/rs") is not groups.lookup(b"t1/rs")
    before = _frontend(sc)
    got = _pipeline(sc.port, _wire("GET", "/s?v=alpha-attack", "t0/rs")
                    + _wire("GET", "/s?v=alpha-attack", "t4/rs"), 2)
    after = _frontend(sc)
    assert [(s, r) for s, r, _a in got] == [(403, "100"), (403, "100")]
    assert after["blob_windows_total"] - before["blob_windows_total"] == 1
    # the cache is keyed by the engine's rule set, for every tenant
    vc = sc.batcher.verdict_cache
    uuid = sc.batcher._cache_uuid(groups.default.engine)
    assert uuid == groups.default.uuid
    assert all(key[0] is None for key in vc._entries)
    assert any(key[1] == uuid for key in vc._entries)


def test_no_tenant_header_is_the_default_tenant(stack):
    _cache, sc = stack
    before = _frontend(sc)
    got = _pipeline(sc.port, _wire("GET", "/d?v=alpha-attack") + _wire("GET", "/d?v=beta-attack"), 2)
    after = _frontend(sc)
    assert [(s, r) for s, r, _a in got] == [(403, "100"), (200, None)]
    assert after["tenant_requests_total"] == before["tenant_requests_total"]
    assert after["python_path_requests_total"] == before["python_path_requests_total"]


def test_unknown_tenant_answers_the_failure_policy_without_a_window(stack):
    _cache, sc = stack
    before, unknown = _frontend(sc), sc.stats()["tenant_groups"]["unknown_total"]
    got = _pipeline(sc.port, _wire("GET", "/u?v=alpha-attack", "nobody/rs")
                    + _wire("GET", "/u?v=alpha-attack", "t0/rs"), 2)
    after = _frontend(sc)
    assert got[0] == (503, None, "fail-closed")
    assert got[1][:2] == (403, "100")
    assert after["blob_windows_total"] - before["blob_windows_total"] == 1  # the known one's
    assert after["tenant_requests_total"] - before["tenant_requests_total"] == 2
    assert after["tenant_blob_requests_total"] - before["tenant_blob_requests_total"] == 1
    assert after["python_path_requests_total"] == before["python_path_requests_total"]
    assert sc.stats()["tenant_groups"]["unknown_total"] == unknown + 1


def test_deadline_header_still_takes_the_python_path(stack):
    _cache, sc = stack
    before = _frontend(sc)
    deadline = ("X-CKO-Deadline-Ms: 5000",)
    got = _pipeline(sc.port, _wire("GET", "/dl?v=beta-attack", "t1/rs", extra=deadline)
                    + _wire("GET", "/dl?v=beta-attack", "t0/rs", extra=deadline), 2)
    after = _frontend(sc)
    assert [(s, r) for s, r, _a in got] == [(403, "200"), (200, None)]
    assert after["python_path_requests_total"] - before["python_path_requests_total"] == 2
    assert after["blob_windows_total"] == before["blob_windows_total"]
    assert after["tenant_blob_requests_total"] == before["tenant_blob_requests_total"]


def test_replies_stay_in_request_order_when_a_read_splits_into_four_windows(stack):
    _cache, sc = stack
    # Each tenant's own word at a position only it blocks: the status
    # sequence is a fingerprint of the order.
    own = {"a": "alpha-attack", "b": "beta-attack", "c": "gamma-attack", "d": "delta-attack"}
    tenants = ["t3/rs", "t0/rs", "t2/rs", "t5/rs", "t1/rs", "t4/rs", "t3/rs", "t2/rs"] * 5
    reqs = [(t, "GET", f"/o?v={own[TENANTS[t]] if i % 3 else 'benign'}&i={i}", b"")
            for i, t in enumerate(tenants)]
    before = _frontend(sc)
    got = _send(sc, reqs)
    after = _frontend(sc)
    want = [_reference(t, m, u, b) for t, m, u, b in reqs]
    assert [(s, r) for s, r, _a in got] == want
    assert len({r for _s, r in want}) == 5  # four rule ids and None
    assert after["blob_windows_total"] - before["blob_windows_total"] >= 4


def test_one_tenants_reload_moves_only_its_own_verdicts(stack):
    cache, sc = stack
    probe = lambda t: _pipeline(sc.port, _wire("GET", "/r?v=omega-attack", t), 1)[0][:2]
    assert [probe(t) for t in ("t1/rs", "t5/rs", "t0/rs")] == [(200, None)] * 3
    old_group = sc.tenants.groups.lookup(b"t1/rs")
    cache.put("t1/rs", TEXTS["b"] + _rule(900, "omega-attack"))
    assert _wait(lambda: sc.tenants.groups.lookup(b"t1/rs") is not old_group)
    assert _wait(lambda: sc.serving_mode() == "promoted")
    try:
        groups = sc.tenants.groups
        assert len(groups.groups) == 5
        # t5 stays on the old text, and now names the group t1 left
        assert groups.lookup(b"t5/rs").engine is old_group.engine
        assert groups.lookup(b"t5/rs").key == "t5/rs"
        assert [probe(t) for t in ("t1/rs", "t5/rs", "t0/rs")] == [
            (403, "900"), (200, None), (200, None)]
        assert probe("t5/rs") == _reference("t5/rs", "GET", "/r?v=omega-attack")
    finally:
        cache.put("t1/rs", TEXTS["b"])
        assert _wait(lambda: len(sc.tenants.groups.groups) == 4)
        assert _wait(lambda: sc.serving_mode() == "promoted")


def test_serving_mode_is_promoted_only_when_every_resident_engine_is(stack):
    _cache, sc = stack
    assert sc.stats()["serving_mode"] == "promoted"
    engine = sc.tenants.groups.lookup(b"t3/rs").engine
    engine.warmed = False
    try:
        assert sc.serving_mode() == "fallback"
        assert sc.serving_mode("t0/rs") == "promoted"  # a tenant's own mode
        assert sc.stats()["tenant_groups"]["groups"]["t3/rs"]["mode"] == "fallback"
    finally:
        engine.warmed = True
    assert sc.serving_mode() == "promoted"


def test_untrusted_header_is_ignored_and_the_default_tenant_answers():
    sc = _start(engine=WafEngine(TEXTS["a"]))
    try:
        before = _frontend(sc)
        got = _pipeline(sc.port, _wire("GET", "/n?v=alpha-attack", "t1/rs")
                        + _wire("GET", "/n?v=beta-attack", "t1/rs")
                        + _wire("GET", "/n?v=beta-attack", "nobody/rs"), 3)
        after = _frontend(sc)
        assert [(s, r) for s, r, _a in got] == [(403, "100"), (200, None), (200, None)]
        assert after["python_path_requests_total"] == before["python_path_requests_total"] == 0
        assert after["tenant_requests_total"] == 0
        assert after["blob_windows_total"] - before["blob_windows_total"] == 1
        assert after["window_reads_total"] - before["window_reads_total"] == 1
        assert after["group_blob_windows"] == {}
        assert not sc.stats()["tenant_groups"]["trusted"]
    finally:
        sc.stop()


@pytest.mark.parametrize("argv, want", [([], False), (["--trust-tenant-header"], True)])
def test_trust_tenant_header_flag_parses_into_the_field(argv, want):
    config = build_config(["--cache-server-instance", "a/b,c/d", *argv])
    assert config.trust_tenant_header is want


# -- the pieces, without a socket -----------------------------------------------------


class _Reloader:
    def __init__(self, engine, uuid):
        self.engine, self.current_uuid = engine, uuid


def test_group_table_lookup_and_whole_swap():
    e1, e2 = object(), object()
    reloaders = {"ns/a": _Reloader(e1, "u1"), "ns/b": _Reloader(e2, "u2"),
                 "ns/c": _Reloader(e1, "u3"), "ns/d": _Reloader(None, None)}
    groups = TenantGroups(reloaders, "ns/a")
    assert [g.key for g in groups.groups] == ["ns/a", "ns/b"]
    assert groups.lookup(None) is groups.lookup(b"") is groups.lookup(b"ns/a") is groups.default
    assert groups.lookup(b"ns/c") is groups.default and groups.default.tenants == ["ns/a", "ns/c"]
    assert groups.lookup(b"/ns/b/").engine is e2 and groups.lookup(b"ns/b").uuid == "u2"
    assert groups.lookup(b"ns/d") is None and b"ns/d" in groups.known  # not loaded
    assert groups.lookup(b"ns/x") is None and b"ns/x" not in groups.known  # unknown
    # the manager swaps the table whole: a reader keeps the one it took
    tm = TenantManager("http://127.0.0.1:1", ["ns/a", "ns/b"], engine_factory=lambda r: r)
    first = tm.groups
    assert first.groups == () and first.lookup(None) is None
    tm.seed("ns/a", e1)
    assert tm.groups is not first and first.groups == ()
    assert tm.groups.default.engine is e1 and tm.resident_engines() == 1
    assert tm.ruleset_uuid_for(e1) is None and tm.ruleset_uuid_for(e2) is None


def test_fair_queue_buckets_a_blob_window_by_its_group():
    group = EngineGroup("ns/b", object(), "u2")
    mk = lambda g: _BlobWindow(blob=b"", n_req=1, fut=Future(), group=g)
    assert _FairQueue._tenant_of(mk(group)) == "ns/b"
    assert _FairQueue._tenant_of(mk(None)) is None
    q = _FairQueue()
    q.put(mk(group)), q.put(mk(None)), q.put(mk(group))
    assert q.tenant_backlog() == {"ns/b": 2, None: 1}


class _StubEngine:
    def __init__(self, name):
        self.name, self.windows = name, []

    def evaluate(self, requests):
        self.windows.append(len(requests))
        return [(self.name, r.uri) for r in requests]


def test_a_window_keeps_its_groups_engine_across_a_reload():
    """The window in flight is judged by the engine its group pinned;
    what ``engine_fn`` returns by then (the reloaded default) is for
    windows that name no group."""
    from coraza_kubernetes_operator_tpu.native import serialize_requests

    old, new = _StubEngine("old"), _StubEngine("new")
    b = MicroBatcher(engine_fn=lambda tenant: new, max_batch_delay_ms=1.0)
    b.start()
    try:
        blob = serialize_requests([HttpRequest(uri="/w1"), HttpRequest(uri="/w2")])
        group = EngineGroup("ns/b", old, "u-old")
        grouped = b.submit_window(blob, 2, lane=LANE_INTERACTIVE, group=group)
        plain = b.submit_window(blob, 2, lane=LANE_INTERACTIVE)
        assert grouped.result(timeout=10) == [("old", "/w1"), ("old", "/w2")]
        assert plain.result(timeout=10) == [("new", "/w1"), ("new", "/w2")]
        assert old.windows == [2] and new.windows == [2]
    finally:
        b.stop()


class _Ctx:
    t_submit, t_accept, window = 1.0, 1.0, None

    def __init__(self):
        self.events = []

    def event(self, name, t0, t1, track=None, args=None):
        self.events.append((name, args))


@pytest.mark.parametrize("ruleset", [None, "uuid-7"])
def test_window_span_names_the_groups_rule_set(ruleset):
    rec = WindowStages(LANE_INTERACTIVE, 3)
    rec.ruleset = ruleset
    ctx = _Ctx()
    rec.trace_onto([ctx], [])
    assert ctx.events and all(
        args.get("ruleset") == ruleset and ("ruleset" in args) == (ruleset is not None)
        for _name, args in ctx.events)
