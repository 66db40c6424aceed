"""The window stage record (observability/stages.py): one record per
device window, stamped where the work happens, read by /waf/v1/stats
``stages``, the flight recorder and the profiler's trace.

Driven three ways: a stub engine through ``MicroBatcher.submit`` (the
per-request window path), the same through ``submit_window`` (the blob
path without a frontend), and real engines behind the async frontend
(the served path): the operator's sample RuleSet, and a rule set that
routes a prefiltered group so that every stage is stamped on the CPU.
"""

import itertools
import json
import socket
import threading
import time
import types
import urllib.request
from pathlib import Path

import pytest

from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
from coraza_kubernetes_operator_tpu.engine.waf import Verdict
from coraza_kubernetes_operator_tpu.observability.stages import (
    DEVICE_STAGES,
    HOST_STAGES,
    STAGES,
    WINDOW_WALL,
    StageStats,
    WindowStages,
)
from coraza_kubernetes_operator_tpu.observability.tracing import PIPELINE_CHAIN
from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar
from coraza_kubernetes_operator_tpu.sidecar.batcher import MicroBatcher

REPO = Path(__file__).resolve().parent.parent
SAMPLE_RULES = (REPO / "wafbench/configs/operator-sample/rules.conf").read_text()
# One group per automata tier; id 101 is prefiltered, so a window's
# record holds prefilter_wait and prefilter_confirm too.
ALL_TIER_RULES = """
SecRuleEngine On
SecDefaultAction "phase:2,log,deny,status:403"
SecRule ARGS|REQUEST_URI "@rx (e|fg)+h" "id:100,phase:2,deny,status:403,t:none"
SecRule ARGS|REQUEST_URI "@rx (a|bc)*a(a|bc){7}d" "id:101,phase:2,deny,status:403,t:none"
SecRule ARGS|REQUEST_URI "@contains evilmonkey" "id:102,phase:2,deny,status:403,t:none"
"""
PREFILTER_STAGES = {"prefilter_wait", "prefilter_confirm"}
FRONTEND_STAGES = {"lane_wait", "loop_hop", "reply_write"}


# -- the record alone ------------------------------------------------------------


def test_stages_share_boundaries_and_sum():
    rec = WindowStages("bulk", n_req=3)
    rec.begin("depth_wait", 10.0)
    rec.end("depth_wait", 10.5)
    rec.begin("route", 10.5)
    t = rec.next("route", "assemble")
    assert rec.spans[-1] == ("route", 10.5, t) and rec.opened("assemble") == t
    rec.end("assemble", t + 2.0)
    assert rec.durations()["assemble"] == pytest.approx(2.0)
    assert rec.total(("depth_wait", "assemble")) == pytest.approx(2.5)
    assert rec.t_first == 10.0 and rec.t_last == t + 2.0


def test_lane_wait_counts_requests_one_stamp_per_read():
    stats = StageStats()
    rec = WindowStages("interactive")
    rec.begin("lane_wait", 100.0)
    # the frontend notes a read when it delivers its first request: here
    # requests 0-2 came in the read stamped 100.0, request 3 in the next
    rec.reads += [[100.0, 0], [100.25, 3]]
    rec.end("lane_wait", 101.0)
    rec.close_lane(4)
    assert rec.reads == [[100.0, 3], [100.25, 1]] and rec.replies_left == 4
    rec.close(stats)
    lane = stats.snapshot()["lane_wait"]["interactive"]
    # n * t_close - sum(t_read)
    assert lane["count"] == 4
    assert lane["sum_s"] == pytest.approx(4 * 101.0 - (3 * 100.0 + 100.25))
    assert sum(lane["buckets"]) == 4


@pytest.mark.parametrize(
    "running, done, want",
    [
        ((), (), "lane_wait"),  # nothing stamped yet
        (("queue_wait",), ("lane_wait",), "queue_wait"),  # left while a stage ran
        ((), ("lane_wait", "queue_wait"), "queue_wait"),  # left between stages
    ],
)
def test_abort_counts_under_the_stage_reached(running, done, want):
    stats = StageStats()
    rec = WindowStages("bulk", n_req=1)
    for s in done:
        rec.begin(s)
        rec.end(s)
    for s in running:
        rec.begin(s)
    rec.abort(stats)
    rec.abort(stats)  # idempotent
    rec.close(stats)  # and an aborted window is never observed
    snap = stats.snapshot()
    assert snap[want]["aborted"] == 1
    assert rec.aborted_at == want
    assert all("bulk" not in v for k, v in snap.items() if k != "buckets_s")


def test_late_stamps_on_a_closed_record_are_dropped():
    """An abandoned window's readback lands late, on another thread."""
    rec = WindowStages("bulk", n_req=1)
    rec.begin("readback_wait")
    rec.abort(None)
    n = len(rec.spans)
    rec.end("readback_wait")  # the worker's __exit__
    rec.begin("decode")
    rec.end("decode")
    assert len(rec.spans) == n


def test_host_and_device_stage_sets_partition_the_engine_stages():
    engine_stages = set(STAGES[STAGES.index("assemble"): STAGES.index("inflight_wait")])
    assert HOST_STAGES == engine_stages
    assert DEVICE_STAGES == {"readback_wait", "decode"}


# -- windows through the batcher and the frontend --------------------------------


class _StubEngine:
    """Two-stage engine without stages of its own: the batcher records
    its whole prepare as ``assemble`` and its whole collect as
    ``readback_wait``."""

    def __init__(self, fail_collect=False):
        self.fail_collect = fail_collect

    def prepare(self, reqs):
        time.sleep(0.004)
        return types.SimpleNamespace(n=len(reqs))

    def prepare_blob(self, _blob, n_req):
        time.sleep(0.004)
        return types.SimpleNamespace(n=n_req)

    def collect(self, inflight):
        time.sleep(0.01)
        if self.fail_collect:
            raise RuntimeError("device fell over")
        return [Verdict(interrupted=False, status=200, rule_id=None)] * inflight.n


def _capture(stage_stats):
    """Every record that ``stage_stats`` observes, in order."""
    seen = []
    observe = stage_stats.observe

    def wrapped(rec):
        seen.append(rec)
        observe(rec)

    stage_stats.observe = wrapped
    return seen


def _burst(port, n, tag, extra_headers=b""):
    """``n`` pipelined GETs in one write: one lane window."""
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        s.sendall(b"".join(
            b"GET /?q=clean%s_%d HTTP/1.1\r\nHost: x\r\n%s\r\n" % (tag, i, extra_headers)
            for i in range(n)
        ))
        got = b""
        while got.count(b"\r\n\r\n") < n:
            chunk = s.recv(65536)
            assert chunk, "connection closed before every reply"
            got += chunk
    return got


def _wait_promoted(sc):
    deadline = time.monotonic() + 120
    while sc.serving_mode() != "promoted" and time.monotonic() < deadline:
        time.sleep(0.02)
    assert sc.serving_mode() == "promoted"


@pytest.fixture(scope="module")
def served():
    """name -> a started async-frontend sidecar on a real engine, with
    every request traced and every observed record kept."""
    out = {}
    for name, rules in (("operator-sample", SAMPLE_RULES), ("all-tiers", ALL_TIER_RULES)):
        sc = TpuEngineSidecar(
            SidecarConfig(host="127.0.0.1", port=0, frontend="async", trace_sample_rate=1.0,
                          adaptive_enabled=False),
            engine=WafEngine(rules),
        )
        seen = _capture(sc.batcher.stage_stats)
        sc.start()
        _wait_promoted(sc)
        _burst(sc.port, 48, b"warm")  # compiles the window's shapes
        out[name] = (sc, seen)
    yield out
    for sc, _seen in out.values():
        sc.stop()


_DRIVES = itertools.count()


def _drive(how, served, n_windows):
    """Run ``n_windows`` windows; returns (records, stages snapshot
    before, after, requests a window)."""
    if how in served:
        sc, seen = served[how]
        before, first = sc.stats()["stages"], len(seen)
        drive = next(_DRIVES)  # a drive made again sends nothing the verdict cache holds
        for k in range(n_windows):
            _burst(sc.port, 48, b"%s%d_%d" % (how.encode(), drive, k))
        deadline = time.monotonic() + 10  # the last reply's writer closes the record
        while len(seen) < first + n_windows and time.monotonic() < deadline:
            time.sleep(0.01)
        return seen[first:], before, sc.stats()["stages"], 48
    b = MicroBatcher(lambda: _StubEngine(), max_batch_size=4, max_batch_delay_ms=1.0)
    seen = _capture(b.stage_stats)
    b.start()
    try:
        before = b.stage_stats.snapshot()
        for k in range(n_windows):
            if how == "stub-submit":
                futs = [b.submit(HttpRequest(uri=f"/?q={k}_{i}")) for i in range(4)]
            else:
                from coraza_kubernetes_operator_tpu.native import serialize_requests

                reqs = [HttpRequest(uri=f"/?q={k}_{i}") for i in range(4)]
                futs = [b.submit_window(serialize_requests(reqs), 4, lane="interactive")]
            for f in futs:
                f.result(timeout=30)
        return seen, before, b.stage_stats.snapshot(), 4
    finally:
        b.stop()


def _grew(before, after, stage, key):
    def total(snap):
        return sum(v[key] for lane, v in snap.get(stage, {}).items() if lane != "aborted")

    return total(after) - total(before)


def _stamped_whole(how, served, n):
    """One drive of ``n`` windows with everything the record promises
    asserted; returns the windows that left more of their wall
    uncovered than a window may."""
    records, before, after, per_window = _drive(how, served, n)
    assert len(records) == n
    want = set(STAGES) - PREFILTER_STAGES - FRONTEND_STAGES
    if how in served:
        want |= FRONTEND_STAGES
    if how == "all-tiers":
        want |= PREFILTER_STAGES
    if how.startswith("stub"):
        # a stub stamps nothing: its prepare is assemble, its collect readback_wait
        want -= {"tier_enqueue", "post_enqueue", "decode"}
    uncovered, over = [], []
    for rec in records:
        assert {s for s, _a, _b in rec.spans} == want, rec.spans
        # stamps are monotone: every span ends after it starts, and
        # starts no earlier than the one stamped before it ended
        assert all(t1 >= t0 for _s, t0, t1 in rec.spans)
        ends = [t1 for _s, _t0, t1 in rec.spans]
        assert ends == sorted(ends)
        assert all(b[1] >= a[2] for a, b in zip(rec.spans, rec.spans[1:])), rec.spans
        assert rec.spans[0][1] == rec.t_first and ends[-1] == rec.t_last
        # and the stages cover the window's wall
        wall = rec.t_last - rec.t_first
        hole = wall - sum(rec.durations().values())
        uncovered.append(hole / wall)
        if hole > max(0.05 * wall, _HANDOFF_S):
            over.append((hole, wall, rec.spans))
        assert rec.n_req == per_window and not rec.aborted_at
    # the windows' median holds the 5% in every drive, loaded or not
    assert sorted(uncovered)[len(uncovered) // 2] <= 0.05, uncovered
    # the cumulative block: count == windows, requests for lane_wait
    for stage in want | {WINDOW_WALL}:
        expect = n * per_window if stage == "lane_wait" else n
        assert _grew(before, after, stage, "count") == expect, stage
        seconds = sum(
            rec.t_last - rec.t_first if stage == WINDOW_WALL else rec.durations()[stage]
            for rec in records
        ) if stage != "lane_wait" else sum(
            rec.durations()["lane_wait"] * rec.n_req for rec in records  # one read a window
        )
        assert _grew(before, after, stage, "sum_s") == pytest.approx(seconds, rel=1e-6)
    for stage in set(STAGES) - want:
        assert _grew(before, after, stage, "count") == 0
    return over


# What one hand-off between two stages' stamps may take when nothing
# preempts it. The walls are 4-9 ms here, so 5% of one is 0.2-0.45 ms:
# under six xdist workers one window in eighty loses 0.3-0.8 ms between
# `lane_wait` and `queue_wait` or at the collector, which is what failed
# `[operator-sample]` in the driver's run of PR 27's tree.
_HANDOFF_S = 1e-3


@pytest.mark.parametrize("how", ["stub-submit", "stub-window", "operator-sample", "all-tiers"])
def test_every_window_is_stamped_whole(how, served):
    # EVERY window's stages sum to its wall within 5% (or one hand-off).
    # A stage that stamps too little leaves its hole in every drive; a
    # hand-off the scheduler preempted for longer than `_HANDOFF_S` does
    # not come again, so a drive with such a window is made once more,
    # and the third in a row fails.
    for _attempt in range(3):
        over = _stamped_whole(how, served, 6)
        if not over:
            return
    pytest.fail(f"stages leave a hole in the window's wall, three drives running: {over}")


def test_batcher_host_and_device_stage_samples_come_from_the_record(served):
    sc, seen = served["all-tiers"]
    n0 = len(seen)
    _burst(sc.port, 24, b"hoststage")
    deadline = time.monotonic() + 10
    while len(seen) == n0 and time.monotonic() < deadline:
        time.sleep(0.01)
    rec = seen[-1]
    assert sc.batcher.stats.host_stage_s[-1] == pytest.approx(rec.total(HOST_STAGES), rel=1e-9)
    assert sc.batcher.stats.device_stage_s[-1] == pytest.approx(
        rec.total(DEVICE_STAGES), rel=1e-9)


@pytest.mark.parametrize("how", ["operator-sample", "all-tiers"])
def test_flight_recorder_carries_the_records_stamps(how, served):
    sc, seen = served[how]
    trace_id = "5e" * 16
    n0 = len(seen)
    _burst(sc.port, 8, b"fr", b"traceparent: 00-%s-%s-01\r\n" % (trace_id.encode(), b"cd" * 8))
    deadline = time.monotonic() + 10
    while len(seen) == n0 and time.monotonic() < deadline:
        time.sleep(0.01)
    rec = seen[-1]
    doc = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{sc.port}/waf/v1/trace?trace_id={trace_id}", timeout=30).read())
    other = doc["otherData"]
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    # the ring holds the 8 requests of the window under one trace id
    assert other["traces"] == 8
    one = events[: len(events) // 8]
    names = [e["name"] for e in one]
    assert [n for n in names if n in PIPELINE_CHAIN] == list(PIPELINE_CHAIN)
    # exported ts is microseconds since the recorder's start
    mono0 = sc.tracer._mono0

    def stamps(name):
        e = next(e for e in one if e["name"] == name)
        return e["ts"] / 1e6 + mono0, (e["ts"] + e["dur"]) / 1e6 + mono0, e["args"]

    def bounds(stages):
        found = [(a, b) for s, a, b in rec.spans if s in stages]
        return found[0][0], found[-1][1]

    for chain, stages in (
        ("assemble", ("route", "assemble")),
        ("dispatch", ("tier_enqueue", "prefilter_wait", "prefilter_confirm", "post_enqueue")),
        ("readback", ("readback_wait",)),
        ("decode", ("decode",)),
    ):
        t0, t1, args = stamps(chain)
        want0, want1 = bounds(stages)
        assert t0 == pytest.approx(want0, abs=2e-6) and t1 == pytest.approx(want1, abs=2e-6)
        assert args["window_id"] == rec.window_id
    # the stages are children inside them, from the same stamps
    for stage, a, b in rec.spans:
        if stage in ("resolve", "loop_hop", "reply_write", "inflight_wait"):
            continue  # stamped after the group's spans were copied
        t0, t1, args = stamps(f"cko.{stage}")
        assert (t0, t1) == (pytest.approx(a, abs=2e-6), pytest.approx(b, abs=2e-6))
        assert args["window_id"] == rec.window_id
    # accept and parse have their own ends; reply spans the window's reply_write
    a0, a1, _ = stamps("accept")
    p0, p1, _ = stamps("parse")
    r0, r1, rargs = stamps("reply")
    assert a0 == pytest.approx(rec.t_first, abs=2e-6) and a1 == pytest.approx(p0, abs=2e-6)
    assert p1 > p0 and r1 >= r0 and rargs["window_id"] == rec.window_id
    assert r0 == pytest.approx(
        next(x for s, x, _ in rec.spans if s == "reply_write"), abs=2e-6)


@pytest.mark.parametrize("via", ["submit", "submit_window"])
def test_a_window_the_device_fails_counts_under_aborted(via):
    b = MicroBatcher(lambda: _StubEngine(fail_collect=True), max_batch_delay_ms=0.5)
    seen = _capture(b.stage_stats)
    b.start()
    try:
        if via == "submit":
            fut = b.submit(HttpRequest(uri="/?q=1"))
        else:
            from coraza_kubernetes_operator_tpu.native import serialize_requests

            fut = b.submit_window(serialize_requests([HttpRequest(uri="/?q=1")]), 1)
        with pytest.raises(RuntimeError):
            fut.result(timeout=30)
        snap = b.stage_stats.snapshot()
    finally:
        b.stop()
    assert not seen  # never observed
    assert snap["readback_wait"]["aborted"] == 1
    assert WINDOW_WALL not in snap


def test_a_shed_window_counts_under_aborted(served, monkeypatch):
    sc, seen = served["operator-sample"]
    n0 = len(seen)
    before = sc.stats()["stages"]["lane_wait"]["aborted"]
    # a backlog over every lane's queue budget: admission control sheds
    monkeypatch.setattr(sc.batcher, "pending", lambda lane=None: 10**9)
    got = _burst(sc.port, 4, b"shed")
    monkeypatch.undo()
    assert got.count(b" 429 ") == 4
    assert sc.stats()["stages"]["lane_wait"]["aborted"] == before + 1
    assert len(seen) == n0


def test_stats_and_metrics_export_the_stages(served):
    sc, _seen = served["all-tiers"]
    stats = sc.stats()
    stages = stats["stages"]
    assert len(stages["buckets_s"]) + 1 == len(stages["decode"]["interactive"]["buckets"])
    assert set(STAGES) | {WINDOW_WALL} <= set(stages)
    assert "memory_peak_bytes" in stats["device"]  # None on the CPU: it reports none
    text = sc.render_metrics()
    assert 'cko_window_stage_seconds_count{stage="prefilter_confirm",lane="interactive"}' in text
    assert 'cko_window_stage_seconds_bucket{stage="window_wall",lane="interactive",le="+Inf"}' \
        in text


def test_stages_are_on_the_profilers_host_plane(served, tmp_path):
    """A jax.profiler capture holds one ``cko.<stage>`` event per stage
    and window, carrying the window id, as long as the record's span."""
    import jax
    from jax.profiler import ProfileData

    sc, seen = served["all-tiers"]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    n0 = len(seen)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for k in range(3):
            _burst(sc.port, 24, b"prof%d" % k)
        deadline = time.monotonic() + 10
        while len(seen) < n0 + 3 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    records = {rec.window_id: rec for rec in seen[n0:]}
    assert len(records) == 3
    data = ProfileData.from_file(str(next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))))
    found: dict[tuple[int, str], float] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("cko."):
                    stats = dict(e.stats)
                    wid = int(stats["window_id"])
                    assert stats["lane"] == "interactive"
                    if wid in records:
                        key = (wid, e.name[4:])
                        found[key] = found.get(key, 0.0) + e.duration_ns / 1e9
    for wid, rec in records.items():
        for stage, seconds in rec.durations().items():
            # the annotation is entered and left beside the stamps
            assert found[(wid, stage)] == pytest.approx(seconds, abs=5e-4), (stage, rec.spans)


def test_no_thread_keeps_a_record_bound():
    from coraza_kubernetes_operator_tpu.observability import stages

    rec = WindowStages("bulk")
    with rec.bound():
        assert stages.current() is rec
    fresh = stages.current()
    assert fresh is not rec and fresh.lane == "direct"
    seen = []
    t = threading.Thread(target=lambda: seen.append(stages.current()))
    with rec.bound():
        t.start()
        t.join(timeout=10)
    assert seen and seen[0] is not rec  # bound to the thread that dispatches it alone
