"""Launcher of the system under test: the shipped tpu-engine command,
unchanged, plus one thread the benchmark can talk to.

    python -m wafbench.sidecar_launch <control dir> -- <tpu_engine arguments>

The main thread runs ``cmd.tpu_engine.main(argv)`` exactly as
``python -m coraza_kubernetes_operator_tpu.cmd.tpu_engine`` would. A
daemon thread blocks on this process's standard input (it costs nothing
while no command comes) and serves three commands, one per line, that
only the process holding the chip can serve:

    memory <name>            peak bytes in use on the fullest local device
    trace_start <name> <dir> <0|1>  jax.profiler.start_trace(dir), Python tracer off or on
    trace_stop <name>               jax.profiler.stop_trace()

Each answer is one JSON file ``<control dir>/<name>.json``, written
whole. The program has no way to do either from its shipped command:
``/waf/v1/profile`` needs a token no flag sets, and nothing reports
device memory (PERF.md, list for the tracing issue).
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path


def _answer(control: Path, name: str, obj: dict) -> None:
    tmp = control / f"{name}.json.tmp"
    tmp.write_text(json.dumps(obj))
    tmp.replace(control / f"{name}.json")


def _serve(control: Path) -> None:
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        cmd, name = words[0], words[1]
        try:
            import jax

            if cmd == "memory":
                peaks = [
                    (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()
                ]
                out = {"memory_peak_bytes": max((p for p in peaks if p is not None), default=None),
                       "devices": len(peaks)}
            elif cmd == "trace_start":
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = int(words[3])
                options.host_tracer_level = 2
                options.enable_hlo_proto = False  # seconds of stop_trace and megabytes, unread
                jax.profiler.start_trace(words[2], profiler_options=options)
                out = {"started_unix_ns": time.time_ns()}
            elif cmd == "trace_stop":
                jax.profiler.stop_trace()
                out = {"stopped_unix_ns": time.time_ns()}
            else:
                out = {"error": f"unknown command {cmd}"}
        except Exception as err:  # boundary: report, keep serving
            out = {"error": repr(err)}
        _answer(control, name, out)


def main(argv: list[str]) -> int:
    split = argv.index("--")
    control = Path(argv[0])
    control.mkdir(parents=True, exist_ok=True)
    threading.Thread(target=_serve, args=(control,), name="wafbench-control", daemon=True).start()
    from coraza_kubernetes_operator_tpu.cmd import tpu_engine

    return tpu_engine.main(argv[split + 1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
