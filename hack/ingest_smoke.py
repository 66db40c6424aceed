#!/usr/bin/env python
"""Async ingest frontend smoke (ISSUE 10 CI satellite).

Drives the SAME request stream over real sockets through two
``TpuEngineSidecar`` instances sharing one ``WafEngine`` — once through
the legacy ``ThreadingHTTPServer`` frontend and once through the
asyncio-native ingest loop (docs/SERVING.md) — with a keep-alive,
pipelined multi-connection client, and asserts:

1. async end-to-end throughput >= RATIO x the threaded frontend
   (default 2.0: the async loop parses once on one core and ships whole
   windows as zero-copy blobs, where the threaded path pays a Python
   thread + HttpRequest materialization per request), and
2. the two frontends' verdicts are BIT-IDENTICAL per request
   (status + x-waf-action + x-waf-rule-id): the frontend is a transport,
   it must never alter a verdict, and
3. when the tiered native window pipeline is built (docs/NATIVE.md), a
   third async pass with CKO_NATIVE_TIERED=0 gates the blob-window
   host-assemble p50: tiered must be >= 2x faster than the legacy
   export + Python tiering on multicore (loud no-regression gate,
   <= 1.15x legacy, on one core), with bit-identical verdicts and
   arena_reuses_total > 0 after warmup.

Usage: ingest_smoke.py [--ratio 2.0] [--requests 2400] [--conns 8]
[--depth 32] (env overrides: INGEST_SMOKE_RATIO / _REQUESTS / _CONNS /
_DEPTH). Exit 0 on pass; 1 with a JSON diagnostic line on fail.
"""

import json
import os
import socket
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _request_bytes(req) -> bytes:
    # Synthetic attack URIs carry raw spaces; a request line must not
    # (both frontends would 400 + close). Encode like a real client.
    uri = req.uri.replace(" ", "%20")
    lines = [f"{req.method} {uri} HTTP/1.1"]
    for k, v in req.headers:
        lines.append(f"{k}: {v}")
    if req.body:
        lines.append(f"Content-Length: {len(req.body)}")
    head = ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1", "replace")
    return head + (req.body or b"")


def _read_response(f):
    status_line = f.readline()
    if not status_line:
        raise ConnectionError("server closed connection mid-stream")
    status = int(status_line.split()[1])
    headers = {}
    while True:
        ln = f.readline()
        if ln in (b"\r\n", b"\n", b""):
            break
        k, _, v = ln.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()
    length = int(headers.get("content-length", 0))
    if length:
        f.read(length)
    return (status, headers.get("x-waf-action"), headers.get("x-waf-rule-id"))


def _conn_worker(port, payloads, depth, out, idx):
    try:
        verdicts = []
        s = socket.create_connection(("127.0.0.1", port), timeout=60)
        try:
            f = s.makefile("rb")
            for i in range(0, len(payloads), depth):
                group = payloads[i : i + depth]
                s.sendall(b"".join(group))
                for _ in group:
                    verdicts.append(_read_response(f))
        finally:
            s.close()
        out[idx] = verdicts
    except BaseException as err:  # surfaced by _drive in the main thread
        out[idx] = err


def _drive(port, payloads, conns, depth):
    """Send payloads over `conns` keep-alive connections (pipelined in
    groups of `depth`); returns (verdicts in request order, wall_s)."""
    shares = [payloads[i::conns] for i in range(conns)]
    out = [None] * conns
    threads = [
        threading.Thread(target=_conn_worker, args=(port, shares[i], depth, out, i))
        for i in range(conns)
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    for r in out:
        if isinstance(r, BaseException):
            raise r
    # Un-stride back to request order.
    verdicts = [None] * len(payloads)
    for i in range(conns):
        verdicts[i::conns] = out[i]
    return verdicts, wall


def main() -> int:
    ratio_env = os.environ.get("INGEST_SMOKE_RATIO")
    ratio = float(ratio_env) if ratio_env else 2.0
    ratio_explicit = ratio_env is not None
    n_requests = int(os.environ.get("INGEST_SMOKE_REQUESTS", "2400"))
    conns = int(os.environ.get("INGEST_SMOKE_CONNS", "8"))
    depth = int(os.environ.get("INGEST_SMOKE_DEPTH", "32"))
    args = sys.argv[1:]
    while args:
        a = args.pop(0)
        if a == "--ratio":
            ratio = float(args.pop(0))
            ratio_explicit = True
        elif a == "--requests":
            n_requests = int(args.pop(0))
        elif a == "--conns":
            conns = int(args.pop(0))
        elif a == "--depth":
            depth = int(args.pop(0))
    single_core = (os.cpu_count() or 1) <= 1
    if single_core and not ratio_explicit:
        # One core = acceptor, batcher, and XLA timeshare: the async
        # win collapses toward parity. The gate degrades (loudly) to
        # "no regression + bit-identical verdicts"; CI runners are
        # multicore and keep the strict 2x bar.
        ratio = 0.9

    os.environ.setdefault("CKO_VALUE_CACHE_MB", "0")
    # Verdict cache OFF (honesty):
    # the timed passes replay the warm pass's stream, so with the
    # fingerprint cache hooked nearly every window is served at
    # assembly — the blob windows would route through the split
    # (materializing) dispatch and the tiered-vs-legacy assemble gate
    # would never see a prepare_blob. Cache speedup has its own smoke
    # (hack/verdict_cache_smoke.py).
    os.environ.setdefault("CKO_VERDICT_CACHE_MAX", "0")
    sys.path.insert(0, str(REPO))
    import jax

    jax.config.update("jax_platforms", "cpu")

    from coraza_kubernetes_operator_tpu.corpus import (
        synthetic_crs,
        synthetic_requests,
    )
    from coraza_kubernetes_operator_tpu.engine.compile_cache import (
        configure_persistent_cache,
    )
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.sidecar import (
        SidecarConfig,
        TpuEngineSidecar,
    )

    configure_persistent_cache(os.environ.get("CKO_COMPILE_CACHE_DIR"))
    eng = WafEngine(synthetic_crs(40, seed=3))
    payloads = [
        _request_bytes(r)
        for r in synthetic_requests(n_requests, attack_ratio=0.2, seed=7)
    ]
    warm = payloads[: min(256, len(payloads))]

    # Three passes over the identical stream: the legacy threaded
    # frontend, the async frontend with the tiered native window
    # pipeline forced OFF (CKO_NATIVE_TIERED=0 -> per-window _export +
    # Python tiering), and the async frontend on the default tiered
    # path (docs/NATIVE.md). threaded-vs-async keeps the original 2x
    # end-to-end gate; async-legacy-vs-async gates the blob-window
    # host-assemble p50 and proves the arena actually recycles.
    native_tiered = eng._native.tiered
    passes = [("threaded", "threaded", None)]
    if native_tiered:
        passes.append(("async-legacy", "async", "0"))
    passes.append(("async", "async", None))

    results = {}
    frontend_stats = {}
    assemble_p50 = {}
    for name, frontend, tiered_env in passes:
        if tiered_env is not None:
            os.environ["CKO_NATIVE_TIERED"] = tiered_env
        sc = TpuEngineSidecar(
            SidecarConfig(
                host="127.0.0.1",
                port=0,
                max_batch_size=128,
                max_batch_delay_ms=2.0,
                frontend=frontend,
            ),
            engine=eng,
        )
        sc.start()
        try:
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline and sc.serving_mode() != "promoted":
                time.sleep(0.05)
            _drive(sc.port, warm, conns, depth)  # untimed warm
            eng.blob_assemble_s.clear()  # steady-state windows only
            verdicts, wall = _drive(sc.port, payloads, conns, depth)
            results[name] = (verdicts, wall)
            frontend_stats[name] = sc.stats().get("frontend", {})
            samples = sorted(eng.blob_assemble_s)
            assemble_p50[name] = (
                samples[len(samples) // 2] if samples else 0.0
            )
        finally:
            sc.stop()
            if tiered_env is not None:
                del os.environ["CKO_NATIVE_TIERED"]

    t_verdicts, t_wall = results["threaded"]
    a_verdicts, a_wall = results["async"]
    identical = a_verdicts == t_verdicts
    if native_tiered:
        identical = identical and a_verdicts == results["async-legacy"][0]
    blocked = sum(1 for v in a_verdicts if v[1] == "deny")
    t_rps = n_requests / max(t_wall, 1e-9)
    a_rps = n_requests / max(a_wall, 1e-9)
    speedup = a_rps / max(t_rps, 1e-9)
    fe = frontend_stats["async"]
    verdict = {
        "req_per_s_threaded": round(t_rps, 1),
        "req_per_s_async": round(a_rps, 1),
        "speedup": round(speedup, 3),
        "required": ratio,
        "requests": n_requests,
        "conns": conns,
        "depth": depth,
        "verdicts_identical": identical,
        "blocked": blocked,
        "async_frontend": {
            "loop": fe.get("loop"),
            "windows": fe.get("windows"),
            "parse_s_per_req": round(
                fe.get("parse_s", 0.0) / max(fe.get("requests_total", 1), 1), 7
            ),
            "bytes_total": fe.get("bytes_total"),
        },
        "cpus": os.cpu_count(),
        "single_core_degraded_gate": single_core and not ratio_explicit,
    }
    ok = speedup >= ratio and identical and blocked > 0

    # Tiered-native host-assemble gate (docs/NATIVE.md): the one-call
    # blob -> arena-tensors pipeline must cut the per-window host
    # assemble p50 >= 2x vs the legacy export + Python tiering on
    # multicore; on one core it degrades (loudly) to no-regression
    # (tiered no worse than 1.15x legacy). The arena must have actually
    # recycled buffers during the tiered pass.
    if native_tiered:
        legacy_p50 = assemble_p50.get("async-legacy", 0.0)
        tiered_p50 = assemble_p50.get("async", 0.0)
        native_speedup = legacy_p50 / max(tiered_p50, 1e-9)
        native_required = 1.0 / 1.15 if single_core else 2.0
        arena = eng.native_stats()["arena"]
        native_ok = (
            native_speedup >= native_required
            and arena["reuses_total"] > 0
        )
        verdict["native"] = {
            "assemble_p50_ms_legacy": round(legacy_p50 * 1e3, 4),
            "assemble_p50_ms_tiered": round(tiered_p50 * 1e3, 4),
            "speedup": round(native_speedup, 3),
            "required": round(native_required, 3),
            "single_core_degraded_gate": single_core,
            "arena": arena,
        }
        ok = ok and native_ok
    else:
        verdict["native"] = "SKIP: tiered pipeline unavailable (make native)"

    verdict["smoke"] = "PASS" if ok else "FAIL"
    print(json.dumps(verdict))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
