"""Read a wafbench cell's window stages by hand, and what recording costs.

    python3 hack/stage_probe.py --workload sample.salted-c2 --seed 7 \
        --windows off,profile,off,profile --seconds 5 [--trace-sample-rate 1]

Starts the cell's sidecar as ``wafbench.harness`` does (the rule texts
of every instance the configuration states and its own ``sidecar_args``,
same traffic, same warm-up; the pieces are the harness's own), but from
the SHIPPED command alone — ``python -m …cmd.tpu_engine`` with
``--metrics-auth-token-file`` — so that the profiler is driven through
``POST /waf/v1/profile`` and device memory read from ``/waf/v1/stats``.
Then one closed-loop window per entry of ``--windows``:

    off      nothing recording but the stage record itself (always on)
    profile  a jax.profiler session active for the whole window (Python
             tracer off), stopped after it; ``stop_s`` is how long that took

Each prints one JSON line: the window's end-to-end numbers from the
client's side, and after − before of ``/waf/v1/stats`` ``stages`` as
milliseconds per window (``lane_wait``: per request), and after − before
of ``automata.prefilter`` (``native_hits`` against ``hits``) and of
``compile_cache`` (``launch_plan_hits`` against ``device_windows``:
every warm window launched from its engine's table; ``launch_plan_misses``
and ``misses`` flat), and of ``tiering`` (the default tenant's engine):
``host_operands`` against ``windows`` and ``tiers``, the host arrays a
window handed to a launch, one transfer each. ``host_operands_per_window``
should read tiers a window + 1 (one match slab a tier, the post slab),
and up to one more a tier where the prefilter confirm repacked the
tier's hit rows (the CRS cells): at most 2 x tiers + 1, where it read
10 to 20 before the slabs. The ``warm`` line carries the same over the
last warm round, the engine's matcher
layout from ``automata`` (``rules``, ``segment_columns`` and
``segment_splits`` / ``segment_split_groups``: the model
the table was read on; ``segment_long_groups``: the groups a tier scans
as DFAs where its plan says ``long``; ``flat_bins``, ``flat_slots``,
``flat_groups``, ``per_bank_kernels``), ``seg_plans`` (how each resident
matcher's conv tier was cut to its budget: ``compile_cache.executables[]
.seg_plan``; ``tiering.long_scan_launches`` beside it counts the launches
that took the long scan), ``device_ops_total`` (what one launch of each
resident executable is made of: ``executables[].device_ops.total``,
parameters not counted) and, from the ``frontend`` counters' growth over
the last warm round, ``tenant_blob_path_share`` and
``engine_windows_per_read`` (also on every window's line, with the
counters themselves under ``frontend``). What
``wafbench.run --trace 1`` reports for the same stages also holds its
traced intervals; this is the untraced reading to hold it against.
The result is no benchmark line: nothing is checked for correctness
beyond "every reply equals its reference verdict".
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from wafbench import harness  # noqa: E402
from wafbench.layer_metrics._window_stages import PER_WINDOW, grew  # noqa: E402

TOKEN = "stage-probe"
LAUNCH_COUNTERS = ("launch_plan_hits", "launch_plan_misses", "device_windows",
                   "host_twin_windows", "hits", "misses", "bypasses")
# The model the table is read on (``automata_summary``): compiled rules,
# the conv tier's columns and the long runs cut into chained pieces, and
# where the engine scans its dense-DFA blocks: fused flat bins, and the
# blocks outside every bin.
MATCHER_LAYOUT = ("rules", "segment_columns", "segment_splits", "segment_split_groups",
                  "segment_long_groups", "flat_bins", "flat_slots", "flat_groups",
                  "per_bank_kernels")
# Growth of these says whether tenant requests rode the blob windows and
# how many windows one socket read closed (sidecar/ingest.py); the two
# benchmark metrics that read them give the ratios.
FRONTEND_COUNTERS = ("window_reads_total", "blob_windows_total", "tenant_requests_total",
                     "tenant_blob_requests_total", "python_path_requests_total")
FRONTEND_METRICS = ("tenant_blob_path_share", "engine_windows_per_read")
TIERING_COUNTERS = ("windows", "tiers", "host_operands", "long_scan_launches", "rows",
                    "rows_padded")


def tiering_growth(before: dict, after: dict) -> dict:
    """after − before of the default engine's ``tiering`` counters, and
    the host operands a window (None where it dispatched none)."""
    out = {k: after["tiering"].get(k, 0) - before["tiering"].get(k, 0)
           for k in TIERING_COUNTERS}
    out["host_operands_per_window"] = (
        out["host_operands"] / out["windows"] if out["windows"] else None)
    return out


def frontend_ratios(cell, before: dict, after: dict) -> dict:
    ctx = {"before": before, "after": after}
    return {m: cell.reader(m).read(ctx) for m in FRONTEND_METRICS}


def stage_ms(before: dict, after: dict) -> dict:
    """after − before of two ``stages`` blocks: ms per window (lane_wait:
    per request), the windows counted, and the share no stage covers."""
    ctx = {"before": {"stages": before}, "after": {"stages": after}}
    windows = grew(ctx, "window_wall", "count")
    if not windows:
        return {"windows": 0}
    out = {"windows": windows, "window_wall": 1e3 * grew(ctx, "window_wall", "sum_s") / windows}
    for stage in ("lane_wait", *PER_WINDOW):
        n = grew(ctx, stage, "count")
        if n:
            out[stage] = 1e3 * grew(ctx, stage, "sum_s") / (n if stage == "lane_wait" else windows)
    staged = sum(v for k, v in out.items() if k not in ("windows", "window_wall"))
    out["unaccounted_share"] = 100.0 * (1.0 - staged / out["window_wall"])
    out["aborted"] = {s: v["aborted"] for s, v in after.items()
                      if s != "buckets_s" and v["aborted"]}
    return out


def profile(port: int, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/waf/v1/profile", data=json.dumps(body).encode(),
        method="POST", headers={"Authorization": f"Bearer {TOKEN}"})
    with urllib.request.urlopen(req, timeout=600) as r:
        return json.loads(r.read())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--windows", default="off,profile,off,profile,off,profile")
    ap.add_argument("--trace-sample-rate", type=float, default=0.0)
    args = ap.parse_args()
    from coraza_kubernetes_operator_tpu.cache import RuleSetCache, RuleSetCacheServer

    cell = harness.Cell(args.workload)
    work = harness.WORK / f"probe-{cell.workload['name']}"
    work.mkdir(parents=True, exist_ok=True)
    lib = harness.WORK / "libcko_native.so"
    subprocess.check_call(["make", "-C", str(REPO / "native"), f"TARGET={lib}"],
                          stdout=subprocess.DEVNULL)
    cache = RuleSetCache()
    server = RuleSetCacheServer(cache, host="127.0.0.1", port=0)
    server.start()
    for instance, text in cell.rules_texts().items():
        cache.put(instance, text)
    token_file = work / "token"
    token_file.write_text(TOKEN + "\n")
    port = harness.free_port()
    # The deployment's own command line (instances, the harness's five
    # flags, the configuration's sidecar_args), then the probe's two.
    argv = cell.sidecar_argv(
        server.port, port,
        None if os.environ.get("JAX_COMPILATION_CACHE_DIR") else harness.WORK / "jax_cache")
    argv += ["--metrics-auth-token-file", str(token_file),
             "--trace-sample-rate", str(args.trace_sample_rate)]
    log_path = work / "sidecar.log"
    with open(log_path, "wb") as fh:
        proc = subprocess.Popen(
            [sys.executable, "-m", "coraza_kubernetes_operator_tpu.cmd.tpu_engine", *argv],
            cwd=REPO, env=dict(os.environ, CKO_NATIVE_LIB=str(lib)), stdout=fh,
            stderr=subprocess.STDOUT)
    sc = harness.Sidecar(port, proc, log_path, work)
    try:
        traffic = cell.traffic(args.seed)
        sc.wait_for("ready", harness.T_READY_S, "readyz",
                    lambda: sc.get("/waf/v1/readyz")[0] == 200)
        sc.wait_for("promotion", harness.T_PROMOTE_S, "promotion",
                    lambda: (s := sc.stats())["serving_mode"] == "promoted"
                    and s["compile_cache"]["inflight"] == 0 and not cell.not_loaded(s))
        harness.send_sequential(sc, traffic, traffic.prime, "prime")
        sc.settle("prime")
        for i in range(harness.WARM_ROUNDS_MAX):
            before = sc.stats()
            w = harness.drive(sc, traffic, None)
            harness.join(w, f"warm{i}", sc)
            after = sc.settle(f"warm{i}")
            if not any(harness.dig(after, k) - harness.dig(before, k) for k in harness.MINTED):
                break
        harness.emit({"phase": "warm", "device": after["device"],
                      "sample_rate": after["tracing"]["sample_rate"],
                      "automata": {k: after["automata"].get(k) for k in MATCHER_LAYOUT},
                      "instances": len(cell.instances()),
                      "resident_engines": after["resident_engines"],
                      "tiering": tiering_growth(before, after),
                      "seg_plans": {e["name"]: e.get("seg_plan")
                                    for e in after["compile_cache"]["executables"]
                                    if e.get("seg_plan")},
                      "device_ops_total": {e["name"]: e["device_ops"]["total"]
                                           for e in after["compile_cache"]["executables"]
                                           if e.get("device_ops")},
                      **frontend_ratios(cell, before, after)})
        for k, mode in enumerate(args.windows.split(",")):
            trace_dir = work / f"trace{k}"
            before = sc.stats()
            if mode == "profile":
                profile(port, {"action": "start", "dir": str(trace_dir)})
            w = harness.drive(sc, traffic, args.seconds)
            harness.join(w, f"window{k}", sc)
            after = sc.stats()
            line = {"window": k, "mode": mode}
            if mode == "profile":
                t0 = time.monotonic()
                profile(port, {"action": "stop"})
                line["stop_s"] = time.monotonic() - t0
                line["trace_bytes"] = sum(f.stat().st_size for f in trace_dir.rglob("*")
                                          if f.is_file())
            numbers = harness.window_numbers(w)
            line.update(numbers["values"], attempted=numbers["attempted"],
                        failed=numbers["failed"],
                        traces_written=after["tracing"]["writes"] - before["tracing"]["writes"],
                        memory_peak_bytes=after["device"]["memory_peak_bytes"],
                        stages_ms=stage_ms(before["stages"], after["stages"]),
                        prefilter={k: v - before["automata"]["prefilter"].get(k, 0) for k, v
                                   in after["automata"]["prefilter"].items()},
                        compile_cache={k: after["compile_cache"][k] - before["compile_cache"][k]
                                       for k in LAUNCH_COUNTERS},
                        tiering=tiering_growth(before, after),
                        frontend={**{k: after["frontend"].get(k, 0) - before["frontend"].get(k, 0)
                                     for k in FRONTEND_COUNTERS},
                                  **frontend_ratios(cell, before, after)})
            harness.emit(line)
        proc.send_signal(signal.SIGTERM)
        return proc.wait(timeout=harness.T_EXIT_S)
    except harness.RunFailure as f:
        harness.emit({"phase": f.phase, "ok": False, "error": f.why, **f.detail})
        return 1
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        server.stop()


if __name__ == "__main__":
    sys.exit(main())
