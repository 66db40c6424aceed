"""The profiler's commands wait under a limit of their own (PR 39): a
``trace_stop`` that answers later than ``T_CONTROL_S`` and sooner than
``T_TRACE_S`` is waited for, and its ``trace`` line says what it cost; one
later than ``T_TRACE_S`` ends the run with exit 1 and says which command,
which limit and how long; ``memory`` is still held to ``T_CONTROL_S``. A
mix that states ``trace_windows`` captures that many device windows,
never longer than its ``trace_seconds``. JAX-free and fast but for the
last two tests, which start sidecars on the CPU (slow, like
``test_served_path.py``).
"""

import json
import subprocess
import sys
import time

import pytest

from wafbench import harness

CRS_CELLS = ["crs-lite.ftw-salted-c1", "crs-lite.ftw-salted-c2", "crs-lite.ftw-repeat80-c1",
             "crs-bodies.api-2k-c1", "crs-custom5k.ftw-salted-c1"]
AS_THEY_WERE = ["sample.salted-c2", "sample.zipf-c2", "tenants32.zipf-salted-c2"]
COST = {"start_s", "capture_s", "stop_s", "windows"}


class StandIn(harness.Sidecar):
    """The launcher's control thread alone (``slow_trace_launch`` without
    ``--``): no sidecar answers ``/waf/v1/stats``, so the device windows
    are a clock's, 100 a second."""

    def stats(self) -> dict:
        return {"compile_cache": {"device_windows": int(100 * time.monotonic())}}


@pytest.fixture
def stand_in(tmp_path, monkeypatch):
    made = []

    def start(**delay_s) -> StandIn:
        monkeypatch.setenv("WAFBENCH_TEST_DELAY_S",
                           ",".join(f"{k}={v}" for k, v in delay_s.items()))
        control = tmp_path / "control"
        control.mkdir()
        proc = subprocess.Popen(
            [sys.executable, "-m", "wafbench.tests.slow_trace_launch", str(control)],
            cwd=harness.REPO, stdin=subprocess.PIPE)
        made.append(proc)
        return StandIn(0, proc, tmp_path / "sidecar.log", control)

    yield start
    for proc in made:
        proc.kill()
        proc.wait()


def test_a_stop_between_the_two_limits_is_waited_for_and_the_line_says_what_it_cost(
        stand_in, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "T_CONTROL_S", 0.3)
    monkeypatch.setattr(harness, "T_TRACE_S", 30.0)
    sc = stand_in(trace_stop=0.8)
    line = harness.traced_interval(sc, tmp_path / "trace", False, 0.2, time.perf_counter())
    assert line["phase"] == "trace" and line["ok"] is True and line["python_tracer"] is False
    assert COST | {"since_window_start_s"} <= set(line)
    assert 0.8 <= line["stop_s"] < 3.0 and 0.2 <= line["capture_s"] < 0.3
    assert 0.0 <= line["start_s"] < 1.0
    # 100 windows a second all through: the capture's share of them, not the stop's
    assert line["windows"] == pytest.approx(100 * line["capture_s"], rel=0.1)


def test_a_stop_later_than_the_trace_limit_fails_and_names_command_limit_and_wait(
        stand_in, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "T_CONTROL_S", 30.0)  # not the one it is held to
    monkeypatch.setattr(harness, "T_TRACE_S", 0.6)
    sc = stand_in(trace_stop=20.0)
    with pytest.raises(harness.RunFailure) as failed:
        harness.traced_interval(sc, tmp_path / "trace", False, 0.05, time.perf_counter())
    f = failed.value
    assert f.phase == "trace_stop" and "waiting for the launcher to answer trace_stop" in f.why
    assert f.detail["command"] == "trace_stop" and f.detail["limit_s"] == 0.6
    assert 0.6 <= f.detail["waited_s"] < 2.0
    json.dumps({"phase": f.phase, "ok": False, "error": f.why, **f.detail})  # as run_cell prints it


def test_memory_is_still_held_to_the_control_limit(stand_in, monkeypatch):
    monkeypatch.setattr(harness, "T_CONTROL_S", 0.4)
    monkeypatch.setattr(harness, "T_TRACE_S", 30.0)
    sc = stand_in(memory=20.0)
    with pytest.raises(harness.RunFailure) as failed:
        sc.command("memory")
    assert failed.value.phase == "memory" and failed.value.detail["limit_s"] == 0.4
    assert failed.value.detail["command"] == "memory"


def test_the_limits_as_shipped():
    assert harness.T_CONTROL_S == 120.0 and harness.T_TRACE_S >= 480.0
    assert (harness.T_READY_S, harness.T_PROMOTE_S, harness.T_SETTLE_S, harness.T_BURST_S,
            harness.T_EXIT_S) == (300.0, 900.0, 900.0, 120.0, 60.0)


@pytest.mark.parametrize("workload", CRS_CELLS)
@pytest.mark.parametrize("window_ms", [15.0, 31.0, 58.0])
def test_a_crs_capture_holds_24_to_40_windows_whatever_a_window_takes(workload, window_ms):
    mix = harness.Cell(workload).mix
    seconds = harness.capture_seconds(mix, 1e3 / window_ms)
    assert seconds <= mix["trace_seconds"]
    assert 24 <= seconds * 1e3 / window_ms <= 40


@pytest.mark.parametrize("workload", CRS_CELLS)
def test_a_capture_is_never_longer_than_the_mixs_cap(workload):
    mix = harness.Cell(workload).mix
    assert harness.capture_seconds(mix, 1e3 / 400.0) == mix["trace_seconds"]  # 400 ms a window
    assert harness.capture_seconds(mix, 0.0) == mix["trace_seconds"]  # no window was served
    assert harness.capture_seconds(mix, None) == mix["trace_seconds"]


@pytest.mark.parametrize("workload", AS_THEY_WERE)
def test_a_mix_without_trace_windows_captures_its_seconds(workload):
    mix = harness.Cell(workload).mix
    assert "trace_windows" not in mix and mix["trace_seconds"] == 4.0
    assert harness.capture_seconds(mix, 66.0) == 4.0
    assert harness.capture_seconds({}, 66.0) == harness.TRACE_SECONDS


# -- whole runs on the CPU (slow: each starts a sidecar) -----------------------------


def traced_run_on_the_cpu(monkeypatch, capfd, stop_delay_s: float):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("WAFBENCH_TEST_DELAY_S", f"trace_stop={stop_delay_s}")
    rc, result = harness.run_cell("sample.salted-c2", seed=2**31 + 39, seconds=3.0, trace=True,
                                  t_process_start=time.monotonic(), rehearse_cpu=True,
                                  device_check=False,
                                  launcher="wafbench.tests.slow_trace_launch")
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines() if ln.startswith("{")]
    return rc, result, lines


def test_a_traced_run_outlasts_the_control_limit_and_reports_its_trace_cost(monkeypatch, capfd):
    monkeypatch.setattr(harness, "T_CONTROL_S", 5.0)  # memory and the rest answer well inside
    monkeypatch.setattr(harness, "T_TRACE_S", 120.0)
    rc, result, lines = traced_run_on_the_cpu(monkeypatch, capfd, stop_delay_s=7.0)
    assert rc == 0 and result["correct"] is True
    traced = [ln for ln in lines if ln.get("phase") == "trace"]
    assert [ln["python_tracer"] for ln in traced] == [False, True]
    assert all(COST <= set(ln) and ln["stop_s"] >= 7.0 for ln in traced)
    assert traced[0]["windows_per_s"] > 0 and traced[0]["windows"] > 0
    assert traced[0]["capture_s"] == pytest.approx(4.0, abs=0.1)  # the mix states no windows
    assert result["trace_cost"] == {
        k: pytest.approx(sum(ln[k] for ln in traced), abs=0.01) for k in COST}
    assert list(result)[-1] == "compared"


def test_a_traced_run_whose_stop_passes_the_trace_limit_exits_1(monkeypatch, capfd):
    monkeypatch.setattr(harness, "T_TRACE_S", 3.0)
    rc, result, lines = traced_run_on_the_cpu(monkeypatch, capfd, stop_delay_s=60.0)
    assert rc == 1 and result is None
    last = lines[-1]
    assert last["phase"] == "trace_stop" and last["ok"] is False
    assert last["error"] == "gave up after 3s waiting for the launcher to answer trace_stop"
    assert last["command"] == "trace_stop" and last["limit_s"] == 3.0 and last["waited_s"] >= 3.0
