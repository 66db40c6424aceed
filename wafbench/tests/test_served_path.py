"""Whole runs on the CPU, at the size a test run can hold (the sample
RuleSet): the result line, the parent's imports, the control, and the
timed path broken underneath. Slow (each run starts a sidecar): run with
``pytest wafbench/tests``; not part of tier-1.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from wafbench import harness

CELL = "sample.salted-c2"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


def run_cli(*extra: str) -> tuple[int, list[dict], str]:
    """The command as the driver starts it, in a fresh process that says
    at exit whether it ever imported jax."""
    code = (
        "import sys; from wafbench import run; rc = run.main(sys.argv[1:]);"
        "print('JAX_IN_PARENT' if any(m == 'jax' or m.startswith('jax.') for m in sys.modules)"
        " else 'NO_JAX_IN_PARENT', file=sys.stderr); sys.exit(rc)"
    )
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--seed", str(2**31 + 17),
         "--seconds", "3", *extra],
        cwd=harness.REPO, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.startswith("{")]
    return p.returncode, lines, p.stderr


@pytest.mark.parametrize("trace", ["0", "1"])
def test_last_line_has_the_contracts_keys_and_the_parent_never_imports_jax(trace):
    rc, lines, err = run_cli("--trace", trace, "--rehearse-cpu")
    assert rc == 0, err[-2000:]
    last = lines[-1]
    assert KEYS <= set(last)
    assert set(last) - KEYS <= {"breakdown", "failed_checks", "compared", "trace_cost"}
    assert ("trace_cost" in last) == (trace == "1")
    assert ("breakdown" in last) == (trace == "1")
    assert last["correct"] is False  # a rehearsal never says true
    assert last["failed_checks"] == ["not_on_tpu"]
    assert last["attempted"] > 0 and last["failed"] == 0
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    group = "per_layer" if trace == "1" else "end_to_end"
    declared = {m["name"]: m["unit"] for m in harness.Cell(CELL).metrics(group)}
    assert set(last["metrics"]) <= set(declared)
    for name, m in last["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == declared[name]
        assert isinstance(m["value"], (int, float))
    if trace == "0":
        assert set(last["metrics"]) == {m["name"] for m in bench["end_to_end"]}
        assert all(m["value"] > 0 for m in last["metrics"].values())
    else:
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert len(last["breakdown"]["device_ops"]) <= 10
        assert len(last["breakdown"]["idle_gaps"]) <= 10
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(last["device"])
    # every number compared is printed beside its limit
    checks = [ln for ln in lines if "check" in ln]
    assert {"verdicts_that_differ", "growth.compile_cache.misses"} <= {c["check"] for c in checks}
    assert all({"value", "limit", "ok"} <= set(c) for c in checks)
    # and again, last in the result line and as the last lines of standard error
    assert list(last)[-1] == "compared"
    assert last["compared"] == {c["check"]: {"value": c["value"], "limit": c["limit"]} for c in checks}
    said = [ln for ln in err.splitlines() if ln.startswith("compared ")]
    assert [ln.split()[1] for ln in said] == [c["check"] for c in checks]
    assert err.splitlines()[-2].startswith("compared ")  # the test's own JAX line comes after
    assert "NO_JAX_IN_PARENT" in err


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    rc, lines, _err = run_cli("--trace", "0")
    assert rc != 0
    assert not any("correct" in ln for ln in lines)


def in_process(**kw):
    rc, result = harness.run_cell(CELL, seed=2**31 + 99, seconds=3.0, trace=False,
                                  t_process_start=time.monotonic(), rehearse_cpu=True,
                                  device_check=False, **kw)
    assert rc == 0
    return result


def test_sound_run_is_correct_and_the_control_is_not():
    sound = in_process()
    assert sound["correct"] is True and sound["failed_checks"] == []
    control = in_process(control=True)  # the configuration's other rule set
    assert control["correct"] is False
    assert "verdicts_that_differ" in control["failed_checks"]
    assert control["failed"] > 0


def test_a_verdict_altered_where_it_is_produced_is_not_correct():
    broken = in_process(launcher="wafbench.tests.broken_launch")
    assert broken["correct"] is False
    assert "verdicts_that_differ" in broken["failed_checks"]
