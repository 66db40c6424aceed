"""A launcher whose control thread answers late. ``WAFBENCH_TEST_DELAY_S``
(``trace_stop=0.8,memory=0.5``) says which commands wait how long before
their answer is written. Used only by ``test_trace_limits.py``.

    python -m wafbench.tests.slow_trace_launch <control dir> -- <tpu_engine arguments>
    python -m wafbench.tests.slow_trace_launch <control dir>

With ``--`` it is the real launcher (the shipped command and its control
thread) and only the answers are held back. Without, it starts nothing
and holds no device: the control thread's three commands alone, answered
from made-up numbers, so that a test of the limits needs neither JAX nor
a sidecar.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

from wafbench import sidecar_launch


def delays() -> dict[str, float]:
    said = os.environ.get("WAFBENCH_TEST_DELAY_S", "")
    return {k: float(v) for k, _, v in (kv.partition("=") for kv in said.split(",") if kv)}


def main(argv: list[str]) -> int:
    wait = delays()
    prompt = sidecar_launch._answer

    def late(control: Path, name: str, obj: dict) -> None:
        time.sleep(wait.get(name.rsplit("-", 1)[0], 0.0))
        prompt(control, name, obj)

    sidecar_launch._answer = late
    if "--" in argv:
        return sidecar_launch.main(argv)
    control = Path(argv[0])
    for line in sys.stdin:
        cmd, name = line.split()[:2]
        late(control, name, {"memory": {"memory_peak_bytes": 1, "devices": 1},
                             "trace_start": {"started_unix_ns": time.time_ns()},
                             "trace_stop": {"stopped_unix_ns": time.time_ns()}}[cmd])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
