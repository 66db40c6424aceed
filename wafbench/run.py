#!/usr/bin/env python3
"""wafbench: one run of one cell of BENCHMARK.json on the chip.

    python3 -m wafbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Every line on standard output is one JSON object; the last is the
result (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, traced, ``breakdown``). A run that finds no TPU, or fewer chips
than the cell asks for, exits non-zero and prints no result.
``--rehearse-cpu`` drives the same run on whatever device JAX finds, for
the sandbox: its result always says ``"correct": false``.
``--control`` serves the configuration's control rule set in place of
its own (``config.json``: ``control``): a sound harness then prints
``"correct": false``.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

if __package__ in (None, ""):  # started as a file: make the checkout importable
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from wafbench import harness  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    rc, result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), T_PROCESS_START,
        rehearse_cpu=args.rehearse_cpu, control=args.control,
    )
    if result is not None:
        if args.rehearse_cpu:
            result["correct"] = False
        harness.emit(result)
    return rc


if __name__ == "__main__":
    sys.exit(main())
