"""``chip_smoke.py`` keeps its contract off the chip (ISSUE 22).

The script is the quickest proof that the served path still starts on a
TPU; here, on the CPU, it is held to everything but the device: with
``--allow-cpu`` on the bundled mini rule set every phase runs and
passes and the last line tells the truth about the device; without the
option it refuses a JAX that is held to the CPU. Plus the import
hygiene the one-process-per-chip rule rests on.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
MINI = ["ftw/rules/base.conf", "ftw/rules/crs-mini.conf"]


def _run(*argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_allow_cpu_runs_every_phase_and_reports_the_cpu():
    out = _run("--allow-cpu", *MINI, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]  # every line is JSON
    *phases, last = lines
    assert all(p["ok"] is True for p in phases), [p for p in phases if not p["ok"]]
    names = [p["phase"] for p in phases]
    for want in ("native_build", "ruleset", "ready", "promotion", "device",
                 "helper", "prime", "warm0", "served", "shutdown"):
        assert want in names, names
    counted = [p for p in phases if p["phase"].startswith("counted")][-1]
    assert counted["failed_checks"] == [] and counted["sent"] >= 100
    assert counted["blocked"] >= 20 and counted["allowed"] >= 20
    assert counted["growth"]["compile_cache.host_twin_windows"] == 0
    assert counted["device_windows"] == sum(counted["windows"].values()) > 0
    assert set(last) == {"ok", "device"}
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_refuses_a_jax_held_to_the_cpu():
    out = _run(*MINI, timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "device" in out.stdout


def test_importing_the_served_path_initialises_no_backend():
    """One process per chip: a parent, client or load generator that
    imports the package must not take the device from the sidecar."""
    code = (
        "import jax\n"
        "import coraza_kubernetes_operator_tpu.engine\n"
        "import coraza_kubernetes_operator_tpu.ftw\n"
        "import coraza_kubernetes_operator_tpu.sidecar.server\n"
        "import coraza_kubernetes_operator_tpu.cmd.tpu_engine\n"
        "assert jax._src.xla_bridge._backends == {}, jax._src.xla_bridge._backends\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, PYTHONPATH=str(REPO)),
    )
    assert out.returncode == 0, out.stderr[-2000:]
