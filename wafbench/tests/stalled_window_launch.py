"""The launcher with device windows that fail under a sound program's
feet, through the program's own fault knobs. While the file
``WAFBENCH_TEST_FAULT_FILE`` names exists:

- ``WAFBENCH_TEST_HANG_S`` (seconds): one readback hangs that long, the
  first, and with ``WAFBENCH_TEST_HANG_EVERY`` n every n-th after it
  (``CKO_FAULT_DEVICE_HANG_S``, one-shot, re-armed by a change of value).
  The watchdog abandons such a window and the host fallback answers it,
  with the right verdicts: what a machine that stands still does.
- ``WAFBENCH_TEST_ERROR_RATE`` (0..1): that share of device dispatches
  raises (``CKO_FAULT_DEVICE_ERROR_RATE``); the fallback answers those too.

Used only by ``test_off_path.py`` and by builders' runs on the chip."""

from __future__ import annotations

import os
import sys
from pathlib import Path

from wafbench import sidecar_launch


def main(argv: list[str]) -> int:
    from coraza_kubernetes_operator_tpu.engine import waf

    flag = Path(os.environ["WAFBENCH_TEST_FAULT_FILE"])
    hang_s = float(os.environ.get("WAFBENCH_TEST_HANG_S", "0"))
    every = int(os.environ.get("WAFBENCH_TEST_HANG_EVERY", "0"))
    rate = os.environ.get("WAFBENCH_TEST_ERROR_RATE", "")
    sound = waf.WafEngine._collect
    seen = [0]

    def _collect(self, inflight):
        if inflight.out is not None and flag.exists():
            seen[0] += 1
            if rate:
                os.environ["CKO_FAULT_DEVICE_ERROR_RATE"] = rate
            if hang_s and (seen[0] == 1 or (every and seen[0] % every == 0)):
                # a value never seen before re-arms the one-shot knob
                os.environ["CKO_FAULT_DEVICE_HANG_S"] = f"{hang_s + seen[0] * 1e-9:.9f}"
        return sound(self, inflight)

    waf.WafEngine._collect = _collect
    return sidecar_launch.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
