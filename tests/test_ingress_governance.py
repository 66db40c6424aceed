"""Ingress resource-governance tests (ISSUE 11 tentpole a+b).

Connection cap (503), slowloris/body read deadlines (408), memory
backpressure (429 with live probes), pipelining bound, drain accounting,
and the ``cko_ingress_*`` observability surface — against real sockets
on both frontends where the contract is shared, per-frontend where the
behavior is documented to differ (the threaded escape hatch closes
timed-out headers silently; the async loop answers 408).
"""

import json
import socket
import time
import urllib.error
import urllib.request

import pytest

from coraza_kubernetes_operator_tpu.engine import WafEngine
from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar

BASE = """
SecRuleEngine On
SecRequestBodyAccess On
SecDefaultAction "phase:2,log,deny,status:403"
"""

EVIL_MONKEY = r"""
SecRule ARGS|REQUEST_URI "@contains evilmonkey" \
  "id:3001,phase:2,deny,status:403,t:none,msg:'Evil Monkey'"
"""


@pytest.fixture(scope="module")
def engine():
    return WafEngine(BASE + EVIL_MONKEY)


def _sidecar(engine, frontend="async", **kw) -> TpuEngineSidecar:
    config = SidecarConfig(
        host="127.0.0.1",
        port=0,
        max_batch_size=kw.pop("max_batch_size", 64),
        max_batch_delay_ms=kw.pop("max_batch_delay_ms", 1.0),
        frontend=frontend,
        **kw,
    )
    return TpuEngineSidecar(config, engine=engine)


def _wait(predicate, timeout_s=30.0, interval_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return False


def _http(port, path, method="GET", body=None, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method, data=body,
        headers=headers or {},
    )
    try:
        resp = urllib.request.urlopen(req, timeout=30)
        return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _read_response(f):
    status_line = f.readline()
    if not status_line:
        return None
    status = int(status_line.split()[1])
    headers = {}
    while True:
        ln = f.readline()
        if ln in (b"\r\n", b"\n", b""):
            break
        k, _, v = ln.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()
    length = int(headers.get("content-length", 0))
    body = f.read(length) if length else b""
    return status, headers, body


def _recv_all(s, timeout=10.0):
    s.settimeout(timeout)
    chunks = []
    while True:
        try:
            data = s.recv(65536)
        except (socket.timeout, ConnectionError):
            break
        if not data:
            break
        chunks.append(data)
    return b"".join(chunks)


# -- connection cap (503) -----------------------------------------------------


@pytest.mark.parametrize("frontend", ["async", "threaded"])
def test_connection_cap_503(engine, frontend):
    sc = _sidecar(engine, frontend=frontend, max_connections=2)
    sc.start()
    try:
        assert _wait(sc.ready)
        held = [
            socket.create_connection(("127.0.0.1", sc.port), timeout=10)
            for _ in range(2)
        ]
        try:
            assert _wait(lambda: sc.governor.connections == 2, 10)
            s3 = socket.create_connection(("127.0.0.1", sc.port), timeout=10)
            raw = _recv_all(s3)
            s3.close()
            assert raw.startswith(b"HTTP/1.1 503"), (frontend, raw[:80])
            assert b"too many connections" in raw
            assert sc.governor.conns_rejected_total >= 1
        finally:
            for s in held:
                s.close()
        # Slots free up once the held connections close.
        assert _wait(lambda: sc.governor.connections == 0, 10)
        status, _, _ = _http(sc.port, "/?q=clean")
        assert status == 200
    finally:
        sc.stop()


# -- read deadlines (slowloris / slow body) -----------------------------------


def test_slowloris_partial_head_408_async(engine):
    sc = _sidecar(engine, header_timeout_s=0.5, idle_timeout_s=10.0)
    sc.start()
    try:
        assert _wait(sc.ready)
        s = socket.create_connection(("127.0.0.1", sc.port), timeout=10)
        try:
            s.sendall(b"GET / HTTP/1.1\r\nHost: slow")  # head never completes
            raw = _recv_all(s)
        finally:
            s.close()
        assert raw.startswith(b"HTTP/1.1 408"), raw[:80]
        assert sc.governor.deadline_closed_total >= 1
    finally:
        sc.stop()


def test_slowloris_partial_head_closes_threaded(engine):
    # The stdlib handler eats the socket timeout inside
    # handle_one_request and closes without a reply — the connection
    # must still be reaped (no slot leak), which is the invariant that
    # matters for the cap.
    sc = _sidecar(engine, frontend="threaded", idle_timeout_s=0.4)
    sc.start()
    try:
        assert _wait(sc.ready)
        s = socket.create_connection(("127.0.0.1", sc.port), timeout=10)
        try:
            s.sendall(b"GET / HTTP/1.1\r\nHost: slow")
            raw = _recv_all(s)
        finally:
            s.close()
        assert raw == b""
        assert _wait(lambda: sc.governor.connections == 0, 10)
    finally:
        sc.stop()


def test_idle_keepalive_closes_silently_async(engine):
    sc = _sidecar(engine, idle_timeout_s=0.3)
    sc.start()
    try:
        assert _wait(sc.ready)
        s = socket.create_connection(("127.0.0.1", sc.port), timeout=10)
        try:
            raw = _recv_all(s, timeout=5.0)  # send nothing at all
        finally:
            s.close()
        assert raw == b""  # idle close is silent, not an error reply
        assert _wait(lambda: sc.governor.connections == 0, 10)
    finally:
        sc.stop()


@pytest.mark.parametrize("frontend", ["async", "threaded"])
def test_slow_body_408_parity(engine, frontend):
    sc = _sidecar(
        engine, frontend=frontend, body_timeout_s=0.5, idle_timeout_s=0.5
    )
    sc.start()
    try:
        assert _wait(sc.ready)
        s = socket.create_connection(("127.0.0.1", sc.port), timeout=10)
        try:
            s.sendall(
                b"POST /submit HTTP/1.1\r\nHost: t\r\nContent-Length: 100\r\n\r\n"
                b"ten bytes."  # then stall forever
            )
            raw = _recv_all(s)
        finally:
            s.close()
        assert raw.startswith(b"HTTP/1.1 408"), (frontend, raw[:80])
        assert b"request body timeout" in raw
        assert sc.governor.deadline_closed_total >= 1
    finally:
        sc.stop()


# -- memory backpressure (429) ------------------------------------------------


@pytest.mark.parametrize("frontend", ["async", "threaded"])
def test_memory_budget_sheds_429_probes_stay_live(engine, frontend):
    sc = _sidecar(
        engine,
        frontend=frontend,
        ingress_memory_budget_bytes=512,
        shed_retry_after_s=3.0,
    )
    sc.start()
    try:
        assert _wait(sc.ready)
        status, headers, body = _http(
            sc.port, "/submit", method="POST", body=b"x" * 600
        )
        assert status == 429, frontend
        assert headers["x-waf-action"] == "shed"
        assert headers["Retry-After"] == "3"
        assert b"overloaded" in body
        assert sc.governor.shed_total >= 1
        # Control endpoints are exempt from the ledger: probes stay
        # green while data-path work sheds.
        assert _http(sc.port, "/waf/v1/healthz")[0] == 200
        assert _http(sc.port, "/waf/v1/readyz")[0] == 200
        # Small requests still fit under the budget.
        status, _, _ = _http(sc.port, "/submit", method="POST", body=b"tiny")
        assert status in (200, 403)
        # Fully discharged — a moment after the reply: the async frontend
        # discharges after the drain (sidecar/ingest.py).
        assert _wait(lambda: sc.governor.inflight_bytes == 0, 10)
    finally:
        sc.stop()


# -- pipelining bound ---------------------------------------------------------


def test_pipelined_burst_over_bound_all_answered_in_order(engine):
    # 300 pipelined requests exceed MAX_PIPELINED (256): the semaphore
    # throttles the reader instead of buffering unboundedly, and every
    # response still arrives, in order.
    sc = _sidecar(engine)
    sc.start()
    try:
        assert _wait(sc.ready)
        n = 300
        payload = b"".join(
            b"GET /?i=%d%s HTTP/1.1\r\nHost: t\r\n%s\r\n"
            % (i, b"&pet=evilmonkey" if i % 7 == 0 else b"",
               b"Connection: close\r\n" if i == n - 1 else b"")
            for i in range(n)
        )
        s = socket.create_connection(("127.0.0.1", sc.port), timeout=60)
        try:
            s.sendall(payload)
            f = s.makefile("rb")
            statuses = []
            for _ in range(n):
                resp = _read_response(f)
                assert resp is not None
                statuses.append(resp[0])
        finally:
            s.close()
        assert statuses == [403 if i % 7 == 0 else 200 for i in range(n)]
        assert _wait(lambda: sc.governor.inflight_bytes == 0, 10)
    finally:
        sc.stop()


# -- drain accounting ---------------------------------------------------------


def test_stop_counts_force_closed_connections(engine):
    sc = _sidecar(engine, drain_timeout_s=0.2)
    sc.start()
    try:
        assert _wait(sc.ready)
        s = socket.create_connection(("127.0.0.1", sc.port), timeout=10)
        s.sendall(b"GET /?q=clean HTTP/1.1\r\nHost: t\r\n\r\n")
        resp = _read_response(s.makefile("rb"))
        assert resp is not None and resp[0] == 200
        # Keep-alive connection still open across stop(): the drain
        # budget expires and the force-close is accounted.
        assert sc.governor.connections >= 1
    finally:
        sc.stop()
    assert sc.governor.aborted_total >= 1
    s.close()


# -- observability surface ----------------------------------------------------


def test_ingress_stats_and_metrics_exposed(engine):
    sc = _sidecar(engine)
    sc.start()
    try:
        assert _wait(sc.ready)
        status, _, body = _http(sc.port, "/waf/v1/stats")
        assert status == 200
        ingress = json.loads(body)["ingress"]
        for key in (
            "connections", "max_connections", "inflight_bytes",
            "memory_budget_bytes", "max_body_bytes", "header_timeout_s",
            "idle_timeout_s", "body_timeout_s", "write_timeout_s",
            "conns_rejected_total", "shed_total", "deadline_closed_total",
            "body_limit_total", "slow_disconnects_total",
            "conn_errors_total", "aborted_total", "window_bytes_pending",
        ):
            assert key in ingress, key
        status, _, body = _http(sc.port, "/waf/v1/metrics")
        assert status == 200
        for name in (
            b"cko_ingress_active_connections",
            b"cko_ingress_max_connections",
            b"cko_ingress_inflight_bytes",
            b"cko_ingress_memory_budget_bytes",
            b"cko_ingress_conns_rejected_total",
            b"cko_ingress_shed_total",
            b"cko_ingress_deadline_closed_total",
            b"cko_ingress_body_limit_total",
            b"cko_ingress_slow_disconnects_total",
            b"cko_ingress_conn_errors_total",
            b"cko_ingest_aborted_total",
        ):
            assert name in body, name
    finally:
        sc.stop()


def test_governor_knob_env_resolution(monkeypatch):
    from coraza_kubernetes_operator_tpu.sidecar.governor import IngressGovernor

    monkeypatch.setenv("CKO_INGRESS_MAX_CONNS", "7")
    monkeypatch.setenv("CKO_INGRESS_HEADER_TIMEOUT_S", "2.5")
    monkeypatch.setenv("CKO_INGRESS_MEMORY_BUDGET_BYTES", "1000")
    gov = IngressGovernor()
    assert gov.max_connections == 7
    assert gov.header_timeout_s == 2.5
    assert gov.memory_budget_bytes == 1000
    # Explicit config wins over env.
    gov = IngressGovernor(max_connections=3, header_timeout_s=1.0)
    assert gov.max_connections == 3
    assert gov.header_timeout_s == 1.0
    # The ledger: charge/discharge with a floor at zero, admission math.
    assert gov.can_admit(999) and not gov.can_admit(1001)
    gov.charge(600)
    assert gov.inflight_bytes == 600
    assert not gov.can_admit(500)
    gov.discharge(700)
    assert gov.inflight_bytes == 0
    # Connection slots.
    assert gov.try_admit_conn() and gov.try_admit_conn() and gov.try_admit_conn()
    assert gov.connections == 3
    assert not gov.try_admit_conn()
    assert gov.conns_rejected_total == 1
    gov.release_conn()
    assert gov.try_admit_conn()
