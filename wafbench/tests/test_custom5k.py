"""The CRS + custom feed deployment (PR 37): its data regenerate byte
for byte from ``freeze_custom``; a rehearsal on the CPU of the same kind
of deployment at a size XLA:CPU compiles (the sample's base behind a
200-rule feed from the same generator, the 46 custom requests of its
picks) ends with every check 0 but the device's, and its control (the
feed removed) is not correct by ``verdicts_that_differ`` alone. Slow (an
engine on 5,269 rules and four passes of the host evaluator; whole runs
that start a sidecar): run with ``pytest wafbench/tests``; not part of
tier-1 (``tests/test_custom_feed.py`` holds the fast half).
"""

import base64
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from wafbench import harness
from wafbench.tools import freeze_custom

CELL = "crs-custom5k.ftw-salted-c1"
BENCH = Path(harness.__file__).resolve().parent
CDIR = BENCH / "configs" / "crs-lite-pl2-custom5k"
MADE = ("corpus.jsonl", "frozen.json", "plans/ftw-custom-salted.json",
        f"rules/{freeze_custom.FEED_FILE}")


@pytest.fixture(scope="module")
def native_lib(tmp_path_factory):
    lib = tmp_path_factory.mktemp("native") / "libcko_native.so"
    subprocess.check_call(["make", "-C", str(harness.REPO / "native"), f"TARGET={lib}"],
                          stdout=subprocess.DEVNULL)
    return lib


def test_the_data_regenerate_byte_for_byte(tmp_path, native_lib):
    configs = tmp_path / "configs"
    shutil.copytree(BENCH / "configs" / "crs-lite-pl2", configs / "crs-lite-pl2")
    copy = configs / CDIR.name
    shutil.copytree(CDIR, copy)
    for made in MADE:
        (copy / made).unlink()
    subprocess.run(
        [sys.executable, "-m", "wafbench.tools.freeze_custom", str(copy)], cwd=harness.REPO,
        check=True, capture_output=True, timeout=3600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", CKO_NATIVE_LIB=str(native_lib)))
    for made in MADE:
        assert (copy / made).read_bytes() == (CDIR / made).read_bytes(), made


# -- whole runs on the CPU, at a size it compiles -------------------------------------------

N_SMALL = 200


def small_checkout(root: Path) -> harness.Cell:
    """A checkout that holds the cell on the sample's two rules behind a
    200-rule feed: the custom requests of the feed's picks (verdicts by
    construction: the rule that blocks, or nothing) in bursts of 4 and 2
    by lane."""
    for d in ("generators", "layer_metrics", "traffic"):
        shutil.copytree(BENCH / d, root / "wafbench" / d)
    cdir = root / "wafbench" / "configs" / CDIR.name
    (cdir / "rules").mkdir(parents=True)
    (cdir / "plans").mkdir()
    shutil.copy(BENCH / "configs" / "operator-sample" / "rules.conf", cdir / "rules" / "base.conf")
    rules = freeze_custom.feed_rules(N_SMALL, 37)
    (cdir / "rules" / freeze_custom.FEED_FILE).write_text(freeze_custom.feed_text(rules))
    config = json.loads((CDIR / "config.json").read_text())
    config["control"]["append"] = f"SecRuleRemoveById 9000000-{9000000 + N_SMALL - 1}"
    (cdir / "config.json").write_text(json.dumps(config))
    customs = freeze_custom.custom_requests(rules, "ckosmoke")
    with open(cdir / "corpus.jsonl", "w") as fh:
        for c in customs:
            wire = freeze_custom.wire_bytes(c["wire"], 300)
            fh.write(json.dumps({
                "id": c["id"], "wire": base64.b64encode(wire).decode(),
                "status": 200 if c["near"] else 403,
                "rule_id": None if c["near"] else str(c["rule"]), "declared": []}) + "\n")
    lanes = {"interactive": [i for i, c in enumerate(customs) if not c["post"]],
             "bulk": [i for i, c in enumerate(customs) if c["post"]]}
    bursts = [{"lane": lane, "requests": mine[k:k + n]}
              for lane, mine, n in (("interactive", lanes["interactive"], 4),
                                    ("bulk", lanes["bulk"], 2))
              for k in range(0, len(mine), n)]
    (cdir / "plans" / "ftw-custom-salted.json").write_text(
        json.dumps({"prime": bursts, "steady": bursts}))
    shutil.copy(harness.REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    return harness.Cell(CELL, root=root)


def run_on_the_cpu(monkeypatch, cell, **kw):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(harness, "Cell", lambda workload: cell)
    rc, result = harness.run_cell(CELL, seed=2**31 + 37, seconds=3.0, trace=False,
                                  t_process_start=time.monotonic(), rehearse_cpu=True,
                                  device_check=False, **kw)
    assert rc == 0
    return result


def test_a_rehearsal_is_correct_and_the_control_is_not(tmp_path, monkeypatch):
    cell = small_checkout(tmp_path / "checkout")
    assert len(cell.traffic(1).connections[0]) == 13  # 40 GETs in fours, 6 POSTs in twos
    sound = run_on_the_cpu(monkeypatch, cell)
    assert sound["correct"] is True and sound["failed_checks"] == []
    assert all(c["value"] == 0 for c in sound["compared"].values())
    assert sound["attempted"] > 46  # every custom request, the 23 blocked under their rule id
    control = run_on_the_cpu(monkeypatch, cell, control=True)
    assert control["correct"] is False
    assert control["failed_checks"] == ["verdicts_that_differ"]
    assert control["compared"]["verdicts_that_differ"]["value"] > 0
