"""The reduction from a profiler trace to numbers: on hand-made events
whose answer is known, and on a recorded excerpt of a chip trace."""

import gzip
import json
from pathlib import Path

import pytest

from wafbench import trace_reduce

MS = 1_000_000  # ns
RECORDED = Path(__file__).parent / "recorded_trace.json.gz"


def events():
    ops = [
        ["while.1", 0 * MS, 10 * MS],      # spans its body: self time 10 - 4 - 3 = 3 ms
        ["fusion.2", 1 * MS, 4 * MS],
        ["fusion.3", 6 * MS, 3 * MS],
        ["copy.4", 20 * MS, 5 * MS],
        ["fusion.2", 40 * MS, 2 * MS],
    ]
    modules = [["jit_match(1)", 0, 10 * MS], ["jit_post(2)", 20 * MS, 5 * MS],
               ["jit_match(1)", 40 * MS, 2 * MS]]
    host = [
        ["python3#0", "$batcher.py:1 step", 0, 50 * MS],           # encloses everything
        ["python3#0", "$waf.py:2 _confirm_prefilter", 10 * MS, 9 * MS],   # fills gap 10..20
        ["python3#0", "$re_dfa.py:9 search", 12 * MS, 1 * MS],     # ... and goes on working in it
        ["python3#1", "$threading.py:3 wait", 9 * MS, 12 * MS],    # waiting: explains nothing
        # a thread parked on the interpreter lock inside a trivial function,
        # tighter over gap 10..20 than the confirm: explains nothing either
        ["python3#2", "$_dtype.py:5 _name", 11 * MS, 8 * MS],
        ["python3#0", "$ingest.py:4 parse", 26 * MS, 13 * MS],     # most of gap 25..40
        ["python3#0", "$ingest.py:6 _head", 30 * MS, 1 * MS],
        ["python3#0", "$batcher.py:7 reply", 43 * MS, 1 * MS],     # the tail 42..50: step goes on
    ]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": modules}], "host": host}


def test_busy_is_the_union_and_idle_the_rest():
    r = trace_reduce.reduce(events())
    assert r["device_plane"] is True and r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.050)
    assert r["busy_s"] == pytest.approx(0.010 + 0.005 + 0.002)


def test_operations_carry_self_time_and_executables_their_runs():
    r = trace_reduce.reduce(events())
    ops = dict(r["device_ops"])
    assert ops["while.1"] == pytest.approx(0.003)
    assert ops["fusion.2"] == pytest.approx(0.004 + 0.002)
    assert ops["fusion.3"] == pytest.approx(0.003)
    assert ops["copy.4"] == pytest.approx(0.005)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    assert r["device_ops"][0][0] == "fusion.2"  # most time first
    assert r["module_busy_s"]["jit_match(1)"] == pytest.approx(0.012)
    assert r["module_runs"] == {"jit_match(1)": 2, "jit_post(2)": 1}


def test_gaps_are_named_by_the_working_host_thread():
    gaps = dict(trace_reduce.reduce(events())["idle_gaps"])
    assert gaps["python3:$waf.py:2 _confirm_prefilter"] == pytest.approx(0.010)
    assert gaps["python3:$ingest.py:4 parse"] == pytest.approx(0.015)
    # the tail 42..50 ms has only the enclosing step over it
    assert gaps["python3:$batcher.py:1 step"] == pytest.approx(0.008)
    assert not any("wait" in k for k in gaps)


def test_two_chips_average_their_busy_time():
    ev = events()
    ev["devices"].append({"name": "/device:TPU:1", "ops": [["fusion.2", 0, 1 * MS]], "modules": []})
    r = trace_reduce.reduce(ev)
    assert r["busy_s"] == pytest.approx((0.017 + 0.001) / 2)


def test_nothing_traced_reads_as_nothing():
    r = trace_reduce.reduce({"devices": [], "host": []})
    assert r["busy_s"] == 0.0 and r["device_plane"] is False


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded chip trace in this checkout")
def test_recorded_chip_trace():
    rec = json.loads(gzip.decompress(RECORDED.read_bytes()))
    r = trace_reduce.reduce(rec["events"])
    want = rec["expected"]
    assert r["device_plane"] is True
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert [n for n, _ in r["device_ops"]] == [n for n, _ in want["device_ops"]]
    assert r["module_runs"] == want["module_runs"]
    assert [n for n, _ in r["idle_gaps"]] == [n for n, _ in want["idle_gaps"]]
    assert any("jit_eval_post_tiered" in name for name in r["module_runs"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(s for _, s in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
