"""The launcher with the timed path broken underneath: the engine's
``collect``, where every window's verdicts are produced, flips the
first verdict of each window. Used only by ``test_served_path.py``."""

from __future__ import annotations

import sys

from wafbench import sidecar_launch


def main(argv: list[str]) -> int:
    from coraza_kubernetes_operator_tpu.engine import waf

    sound = waf.WafEngine.collect

    def collect(self, inflight):
        verdicts = sound(self, inflight)
        if verdicts:
            v = verdicts[0]
            verdicts[0] = waf.Verdict(
                interrupted=not v.interrupted, status=200 if v.interrupted else 403,
                rule_id=None if v.interrupted else 1,
            )
        return verdicts

    waf.WafEngine.collect = collect
    return sidecar_launch.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
