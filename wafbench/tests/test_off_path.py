"""What ``correct`` allows off the device path (PR 39, second session): a
window that the watchdog abandons on a machine standing still is answered
by the host fallback with the right verdicts, so the counters it moves
(``harness.OFF_PATH``) are held to ``OFF_PATH_SHARE`` of the window's
requests or device windows; the answers and every other counter stay
exact. The arithmetic is JAX-free and fast; the last two tests start
sidecars on the CPU with the program's own fault knobs (slow, like
``test_served_path.py``).
"""

import threading
import time

import pytest

from wafbench import harness

CHAIN = ("degraded.fallback_requests", "watchdog.windows_abandoned", "batcher.errors",
         "compile_cache.misses", "compile_cache.host_twin_windows")


def stats(cell, windows=0, requests=0, **grown) -> dict:
    """A ``/waf/v1/stats`` with every counter the cell's mix compares at
    0 but those named (dotted, ``.`` as ``__``)."""
    s = {"serving_mode": "promoted",
         "tenants": {i["instance"].strip("/"): {"loaded": True} for i in cell.instances()},
         "degraded": {"breaker": {"state": "closed"}}, "batcher": {"requests": requests}}
    for key in [*cell.mix["zero_growth"], harness.DEVICE_WINDOWS]:
        d = s
        *path, leaf = key.split(".")
        for k in path:
            d = d.setdefault(k, {})
        d.setdefault(leaf, grown.get(key.replace(".", "__"), 0))
    s["compile_cache"]["device_windows"] = windows
    return s


def failed(workload, attempted, windows, differ=0, lost=0, requests=None, **grown) -> list[str]:
    cell = harness.Cell(workload)
    numbers = {"attempted": attempted, "differ": differ, "lost": lost}
    after = stats(cell, windows, attempted if requests is None else requests, **grown)
    compared = harness.comparisons(cell, stats(cell), after, numbers, True, True)
    assert all(limit == 0 for name, _v, limit in compared
               if name.removeprefix("growth.") not in harness.OFF_PATH
               and name != "batcher_requests_minus_attempted")
    return [name for name, value, limit in compared if value > limit]


# (cell, attempted, device windows, requests in one window, rows the device was given)
RUNS = [("crs-lite.ftw-repeat80-c1", 88170, 2939, 30, 6),   # the refused run's size (traced)
        ("crs-lite.ftw-repeat80-c1", 34230, 1141, 30, 6),   # untraced, 20 s
        ("crs-custom5k.ftw-salted-c1", 2387, 341, 7, 7),    # the fewest requests a cell sends
        ("crs-bodies.api-2k-c1", 3800, 760, 5, 5),
        ("tenants32.zipf-salted-c2", 86000, 2700, 32, 32),
        ("sample.salted-c2", 135000, 1055, 128, 128)]


@pytest.mark.parametrize("workload,attempted,windows,n_req,rows", RUNS)
def test_one_abandoned_window_and_what_follows_from_it_is_correct(
        workload, attempted, windows, n_req, rows):
    assert failed(workload, attempted, windows) == []
    assert failed(workload, attempted, windows, requests=attempted - rows,
                  degraded__fallback_requests=n_req, batcher__errors=n_req,
                  watchdog__windows_abandoned=1, compile_cache__misses=1,
                  compile_cache__host_twin_windows=1) == []


@pytest.mark.parametrize("workload,attempted,windows,n_req,rows", RUNS)
def test_a_twentieth_of_the_windows_off_the_device_path_is_not(
        workload, attempted, windows, n_req, rows):
    off = windows // 20
    names = failed(workload, attempted, windows, requests=attempted - off * rows,
                   degraded__fallback_requests=off * n_req, batcher__errors=off * n_req,
                   watchdog__windows_abandoned=off)
    assert {"growth.degraded.fallback_requests", "growth.batcher.errors",
            "growth.watchdog.windows_abandoned"} <= set(names)


@pytest.mark.parametrize("grown", ["failopen_total", "shed_total", "quarantine__isolated_total",
                                   "compile_cache__bypasses", "verdict_cache__hits_total",
                                   "verdict_cache__window_dedup_rows"])
def test_every_other_counter_is_exact(grown):
    assert failed("crs-lite.ftw-salted-c1", 8000, 1300, **{grown: 1}) == \
        ["growth." + grown.replace("__", ".")]


@pytest.mark.parametrize("wrong", ["differ", "lost"])
def test_the_answers_are_exact(wrong):
    assert failed("crs-lite.ftw-salted-c1", 8000, 1300, **{wrong: 1}) == \
        ["verdicts_that_differ" if wrong == "differ" else "requests_unanswered"]


def test_a_run_too_short_to_hold_a_share_is_exact_again():
    assert failed("crs-lite.ftw-salted-c1", 60, 10, watchdog__windows_abandoned=1) == \
        ["growth.watchdog.windows_abandoned"]
    assert failed("crs-lite.ftw-salted-c1", 60, 10, degraded__fallback_requests=1) == \
        ["growth.degraded.fallback_requests"]


def test_the_share_is_a_hundredth_and_covers_the_chain_and_nothing_else():
    assert harness.OFF_PATH_SHARE == 0.01
    assert set(harness.OFF_PATH) == set(CHAIN)
    for workload in {r[0] for r in RUNS}:
        assert set(CHAIN) <= set(harness.Cell(workload).mix["zero_growth"])


# -- whole runs on the CPU, the program's fault knobs under a sound program -------------


def faulted_run(monkeypatch, tmp_path, seconds, **knobs):
    flag = tmp_path / "fault.flag"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("WAFBENCH_TEST_FAULT_FILE", str(flag))
    for k, v in knobs.items():
        monkeypatch.setenv(f"WAFBENCH_TEST_{k.upper()}", str(v))
    drive = harness.drive

    def drive_then_fault(sc, traffic, window_s):
        w = drive(sc, traffic, window_s)
        if window_s is not None:  # the measured window alone, a second in
            threading.Timer(1.0, flag.touch).start()
        return w

    monkeypatch.setattr(harness, "drive", drive_then_fault)
    rc, result = harness.run_cell(
        "sample.salted-c2", seed=2**31 + 7, seconds=seconds, trace=False,
        t_process_start=time.monotonic(), rehearse_cpu=True, device_check=False,
        launcher="wafbench.tests.stalled_window_launch")
    assert rc == 0
    return result


def test_a_window_abandoned_on_a_hang_is_answered_right_and_the_run_is_correct(
        monkeypatch, tmp_path):
    # the CPU's watchdog deadline is ten times its p99 step: some seconds
    result = faulted_run(monkeypatch, tmp_path, 16.0, hang_s=12)
    compared = result["compared"]
    assert compared["growth.watchdog.windows_abandoned"]["value"] == 1
    assert compared["growth.degraded.fallback_requests"]["value"] > 0
    assert compared["verdicts_that_differ"]["value"] == 0
    assert result["correct"] is True, result["failed_checks"]


def test_a_twentieth_of_the_dispatches_failing_is_not_correct(monkeypatch, tmp_path):
    result = faulted_run(monkeypatch, tmp_path, 6.0, error_rate=0.05)
    assert result["compared"]["verdicts_that_differ"]["value"] == 0
    assert result["correct"] is False
    assert "growth.degraded.fallback_requests" in result["failed_checks"]
