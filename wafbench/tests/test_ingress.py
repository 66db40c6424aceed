"""CRS behind a gateway with a hundred requests in flight (PR 45):
``crs-lite-pl2-ingress`` and its cell ``crs-ingress.wide-u512-c1``, found
by name. The rule text is ``crs-lite-pl2``'s byte for byte; the pool is
its 238 interactive-lane go-ftw requests under the verdicts its own table
holds and 770 header-only synthetic ``GET``s (1,008 = 9 x 112: ISSUE 45's
1,024 cannot be sent once a pass in bursts of 96-112 requests and 448-512
rows at the pool's four rows a request); every steady burst and every
prime group is one window whose wide tier is ``512x512``, inside one
socket read, and a pass sends every pool request once; the control
(the 942 family removed) differs from the reference; the two readers the
cell brings, on hand-made ``/waf/v1/stats`` snapshots; and
``freeze_ingress`` makes the same pool twice on a 32-request slice, which
is the committed pool's. All JAX-free but the control's and the slice's
(the plain host evaluator on crs-lite's 269 rules). The slow one: the
data regenerate byte for byte (run with ``pytest wafbench/tests``).
Tier-1 imports the others through ``tests/test_wafbench_ingress.py``.
"""

import base64
import filecmp
import json
import os
import shutil
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

from wafbench import harness
from wafbench.generators.planned_bursts import SALT_TOKEN
from wafbench.tools import freeze_ingress

CELL = "crs-ingress.wide-u512-c1"
BENCH = Path(harness.__file__).resolve().parent
CDIR = BENCH / "configs" / "crs-lite-pl2-ingress"
BASE = BENCH / "configs" / "crs-lite-pl2"
MADE = ("corpus.jsonl", "frozen.json", "plans/wide-u512.json")


def pool() -> list[dict]:
    return [json.loads(line) for line in open(CDIR / "corpus.jsonl")]


def plan() -> dict:
    return json.loads((CDIR / "plans" / "wide-u512.json").read_text())


def spec() -> dict:
    return json.loads((CDIR / "freeze.json").read_text())


def same_tree(a: Path, b: Path) -> bool:
    cmp = filecmp.dircmp(a, b)
    if cmp.left_only or cmp.right_only or cmp.funny_files:
        return False
    _same, differ, errors = filecmp.cmpfiles(a, b, cmp.common_files, shallow=False)
    return not differ and not errors and all(same_tree(a / d, b / d) for d in cmp.common_dirs)


def test_the_cell_resolves_and_states_its_deployment():
    cell = harness.Cell(CELL)
    assert cell.config["name"] == cell.config_dir.name == "crs-lite-pl2-ingress"
    assert cell.config["architecture"] is None and "sidecar_args" not in cell.config
    entry = next(c for c in cell.bench["configs"] if c["name"] == cell.config["name"])
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cell.config["reduced"] == list(cell.config["reduced_why"]) == [
        "ingress_100k_qps_cut_to_one_connection_one_window_closed_loop",
        "window_2048_requests_cut_to_one_64KiB_read_100_requests_512_rows"]
    assert cell.workload["chips"] == 1 and cell.workload["traffic"] == "wide-u512-c1"
    lite = harness.Cell("crs-lite.ftw-salted-c1")
    assert cell.config["guarantees"] == lite.config["guarantees"]  # word for word
    assert cell.config["control"]["append"] == lite.config["control"]["append"]
    assert cell.mix["zero_growth"] == lite.mix["zero_growth"]
    assert "verdict_cache.hits_total" in cell.mix["zero_growth"]
    assert cell.mix["connections"] == [{"lanes": ["interactive"]}]
    assert cell.mix["generator"] == "planned_bursts" and cell.mix["plan"] == "wide-u512"
    assert cell.mix["salt_hex"] == spec()["salt_hex"] == 300
    assert 6 <= cell.mix["trace_windows"] <= 26 and cell.mix["trace_seconds"] == 2.0
    # the argv is crs-lite's: the harness's own five and nothing else
    assert cell.sidecar_argv(1, 2, None) == lite.sidecar_argv(1, 2, None)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"matcher_device_us_per_row", "row_padding_share", "matcher_device_ms_per_window",
            "device_idle_share", "assemble_ms_per_window", "window_requests_mean"} <= names
    for w in cell.bench["workloads"]:
        if w["name"] != CELL:
            theirs = {m["name"] for m in harness.Cell(w["name"]).metrics("per_layer")}
            assert not {"matcher_device_us_per_row", "row_padding_share"} & theirs
    control = cell.rules_text(control=True)
    assert control.endswith("\nSecRuleRemoveById 942100-942999\n")
    assert control.startswith(cell.rules_text())


def test_the_rule_text_is_crs_lite_pl2s_byte_for_byte():
    assert same_tree(CDIR / "rules", BASE / "rules")
    assert len(list((CDIR / "rules").glob("*.conf"))) == 23 and (CDIR / "rules" / "data").is_dir()
    assert harness.Cell(CELL).rules_text().split("\n", 1)[1] == \
        harness.Cell("crs-lite.ftw-salted-c1").rules_text().split("\n", 1)[1]


def test_the_pool_is_the_interactive_go_ftw_requests_and_770_synthetic_gets():
    rows, frozen, s = pool(), json.loads((CDIR / "frozen.json").read_text()), spec()
    assert len(rows) == s["pool_requests"] == 1008 and len({r["id"] for r in rows}) == 1008
    ftw, syn = rows[:238], rows[238:]
    theirs = [json.loads(line) for line in open(BASE / "corpus.jsonl")]
    sent = sorted({i for b in json.loads((BASE / "plans" / "ftw-salted.json").read_text())["steady"]
                   if b["lane"] == "interactive" for i in b["requests"]})
    # the same requests under the same verdicts, in the base's order
    assert [(r["id"], r["wire"], r["status"], r["rule_id"], r["declared"]) for r in ftw] == \
        [(theirs[i]["id"], theirs[i]["wire"], theirs[i]["status"], theirs[i]["rule_id"],
          theirs[i]["declared"]) for i in sent]
    assert sum(r["status"] != 200 for r in ftw) == frozen["ftw_blocked"] == 192
    assert [r["id"] for r in syn] == [f"syn-{i}" for i in range(770)]
    made = freeze_ingress.synthetic_requests(770, s)
    assert [base64.b64decode(r["wire"]) for r in syn] == [m["wire"] for m in made]
    for r in rows:
        wire = base64.b64decode(r["wire"])
        head, _, body = wire.partition(b"\r\n\r\n")
        assert not body and wire.count(SALT_TOKEN) == 1
        assert b"ckosmoke=" + SALT_TOKEN + b" HTTP/1.1" in head.split(b"\r\n")[0]
    for r in syn:
        head = base64.b64decode(r["wire"]).decode()
        assert head.startswith("GET /") and "\r\nCookie: session=" in head
        assert "\r\nUser-Agent: Mozilla/5.0 (" in head and "\r\nAccept: */*\r\n" in head
    attacks = [m["attack"] for m in made]
    assert sum(attacks) == frozen["synthetic_attacks"] == 51  # 0.05 of 770, as drawn
    # CRS at PL2 blocks every synthetic attack and no synthetic benign request
    assert [r["status"] != 200 for r in syn] == attacks
    assert frozen["synthetic_benign_blocked"] == 0 and frozen["synthetic_attacks_blocked"] == 51
    assert frozen["blocked"] == 243 and frozen["allowed"] == 765  # 24.1% of the pool
    assert frozen["left_out"]["salt_moves_verdict"] == 0 and len(frozen["left_out"]["salt_seeds"]) == 4
    assert frozen["rules_compiled"] == 269 and frozen["automata_summary"]["segment_columns"] == 2496


def test_every_burst_and_prime_group_is_one_512x512_window_inside_one_read():
    p, rows, s = plan(), pool(), spec()
    assert p["tier_shapes"] == [[512, 512]] == [s["tier_shape"]]
    steady, prime = p["steady"], p["prime"]
    assert len(steady) == 9 and p["requests_per_pass"] == 1008
    sent = Counter(i for b in steady for i in b["requests"])
    assert set(sent) == set(range(1008)) and set(sent.values()) == {1}  # each once a pass
    assert s["burst_requests"] == [96, 112]  # ISSUE 45's range
    for b in steady:
        assert b["lane"] == "interactive" and 96 <= len(b["requests"]) <= 112
        # the short rows' tier, every row of it cached: one padding row; then the wide launch
        assert b["tier_shapes"] == [[1, 64], [512, 512]] and b["tier_rows"][0] == 0
        assert s["miss_lo"] <= b["unique_uncached_rows"] == b["tier_rows"][1] <= s["miss_hi"]
        assert b["unique_uncached_rows"] == 4 * len(b["requests"])  # the salt's four rows
        wire = sum(freeze_ingress.wire_bytes(base64.b64decode(rows[i]["wire"]), 300)
                   for i in b["requests"])
        assert wire == b["wire_bytes"] <= s["wire_bytes_max"] == 61440 < 65536
        # about a quarter of it go-ftw, as the pool is
        assert b["ftw_requests"] == sum(i < 238 for i in b["requests"]) and 24 <= b["ftw_requests"] <= 30
    assert len({str(b["post_shapes"]) for b in steady}) == 1  # one post stage for all
    # the prime pass: every pool request once from a cold cache, then the first steady burst
    assert 10 <= len(prime) <= s["prime_groups_max"] + 1
    assert prime[-1]["requests"] == steady[0]["requests"]
    assert prime[-1]["tier_shapes"] == steady[0]["tier_shapes"]
    assert prime[-1]["post_shapes"] == steady[0]["post_shapes"]
    cold = Counter(i for b in prime[:-1] for i in b["requests"])
    assert set(cold) == set(range(1008)) and set(cold.values()) == {1}
    lo, hi = s["prime_narrow_rows"]
    for b in prime[:-1]:
        # cold, the short rows are a second launch, held to one bucket of rows
        assert b["tier_shapes"] == [[256, 64], [512, 512]] and b["lane"] == "interactive"
        assert lo <= b["tier_rows"][0] <= hi and 256 < b["tier_rows"][1] <= 512
        assert b["wire_bytes"] <= s["wire_bytes_max"]
    frozen = json.loads((CDIR / "frozen.json").read_text())
    assert frozen["second_matcher_shapes"]["prime"] == [[256, 64]]
    assert frozen["second_matcher_shapes"]["steady"] == [[1, 64]]
    assert frozen["second_matcher_shapes"]["why"] == s["second_matcher_shapes_why"]
    assert frozen["plan"]["steady_executable_sets"] == 1
    # 512x512 on 448 rows and the short tier's padding launch
    assert frozen["plan"]["steady_matcher_launches_a_burst"] == 2
    assert frozen["plan"]["steady_matcher_rows_a_burst"] == ["[0, 448]"]
    cell = harness.Cell(CELL)
    t = cell.traffic(2**31 + 45)
    assert len(t.connections) == 1 and len(t.connections[0]) == 9 and len(t.prime) == len(prime)
    assert sorted(b.n for b in t.connections[0]) == sorted(len(b["requests"]) for b in steady)
    wire = t.salted(t.connections[0][0], "c0")
    assert SALT_TOKEN not in wire and wire.count(b" HTTP/1.1\r\n") == t.connections[0][0].n
    assert len(wire) == steady[0]["wire_bytes"]
    blocked = sum(want[0] != 200 for b in t.connections[0] for want in b.expected)
    assert blocked == 243


@pytest.fixture(scope="module")
def host_engines():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine

    cell = harness.Cell(CELL)
    return WafEngine(cell.rules_text()), WafEngine(cell.rules_text(control=True))


def test_the_control_lets_through_what_the_942_family_alone_blocked(host_engines):
    from wafbench.tools.freeze_bodies import materialize

    rows = pool()
    reqs = [materialize(base64.b64decode(r["wire"]), b"ab" * 150) for r in rows]
    got = [(v.status if v.interrupted else 200, str(v.rule_id or 0) if v.interrupted else None)
           for v in host_engines[1].host_fallback.evaluate(reqs)]
    differ = [(r, g) for r, g in zip(rows, got) if g != (r["status"], r["rule_id"])]
    # anomaly scoring: what the SQL injection family alone carried over the
    # threshold is let through; nothing is blocked that was not
    assert len(differ) == 67 and all(r["status"] == 403 and g == (200, None) for r, g in differ)
    # of both parts of the pool: a run's control sees them in every burst
    assert sum(r["id"].startswith("syn-") for r, _ in differ) == 24


def test_freeze_ingress_is_deterministic_on_a_32_request_slice(host_engines):
    s = spec()
    made = []
    for _ in range(2):
        raw = freeze_ingress.raw_pool(s, CDIR.parent, 32)
        got, left_out = freeze_ingress.reference(host_engines[0], raw, s, 32)
        made.append([freeze_ingress.corpus_line(r) for r in got])
        assert left_out["salt_moves_verdict"] == 0 and left_out["spare_not_needed"] == s["pool_spare"]
    assert made[0] == made[1] and len(made[0]) == 32
    # the slice is the committed pool's first 8 go-ftw and first 24 synthetic requests
    lines = (CDIR / "corpus.jsonl").read_text().splitlines()
    assert made[0] == lines[:8] + lines[238:238 + 24]
    order = freeze_ingress.interleave([json.loads(x) | ({"attack": 0} if k >= 8 else {})
                                       for k, x in enumerate(made[0])], s["seed"])
    assert sorted(order) == list(range(32))
    assert [sum(i < 8 for i in cut) for cut in freeze_ingress.equal_cuts(order, 4)] == [2, 2, 2, 2]


# -- the readers the cell brings ----------------------------------------------------------


def stats(windows, rows, padded, **tiering):
    return {"tiering": {"windows": windows, "tiers": 2 * windows, "rows": rows,
                        "rows_padded": padded, **tiering}}


def test_row_padding_share():
    reader = harness.Cell(CELL).reader("row_padding_share")
    assert reader.SOURCE == "program_counter"
    # 100 windows of 448 rows on 512, the cached tier's one padding row beside them
    ctx = {"before": stats(10, 4000, 5000), "after": stats(110, 4000 + 44800, 5000 + 51300)}
    assert reader.read(ctx) == pytest.approx(100 * (1 - 44800 / 51300))
    assert reader.read({"before": stats(10, 5, 8), "after": stats(10, 5, 8)}) is None  # no window
    parent = {"tiering": {"windows": 5, "tiers": 5, "cells": 9, "real_bytes": 3}}
    assert reader.read({"before": parent, "after": parent}) is None  # no counter, no raise
    assert reader.read({"before": {}, "after": {}}) is None


def test_matcher_device_us_per_row():
    reader = harness.Cell(CELL).reader("matcher_device_us_per_row")
    per_window = harness.Cell(CELL).reader("matcher_device_ms_per_window")
    assert reader.SOURCE == "device_trace"
    post, match = "jit_cko_eval_post_1x64_512x512(3)", "jit_cko_match_512x512(7)"
    runs, t = [], 0.0
    for _ in range(5):  # five windows: a matcher of 40 ms, then its post stage
        runs += [(match, t, 0.040), (post, t + 0.041, 0.002)]
        t += 0.060
    trace = {"module_events": [runs], "module_busy_s": {}, "module_runs": {}}
    ctx = {"before": stats(10, 4000, 5000), "after": stats(60, 4000 + 50 * 448, 5000 + 50 * 513),
           "trace": trace}
    # four whole windows between the first post stage's end and the last's
    assert per_window.read(ctx) == pytest.approx(40.0)
    assert reader.read(ctx) == pytest.approx(40000.0 / 448)
    assert reader.read(dict(ctx, trace={"module_events": [], "module_busy_s": {},
                                        "module_runs": {}})) is None
    same = stats(10, 4000, 5000)
    assert reader.read({"before": same, "after": same, "trace": trace}) is None  # no window
    parent = {"tiering": {"windows": 5, "tiers": 5}}
    assert reader.read({"before": parent, "after": dict(parent), "trace": trace}) is None
    assert reader.read({"before": {}, "after": {}, "trace": trace}) is None


# -- slow: the data regenerate ----------------------------------------------------------------


@pytest.fixture(scope="module")
def native_lib(tmp_path_factory):
    lib = tmp_path_factory.mktemp("native") / "libcko_native.so"
    subprocess.check_call(["make", "-C", str(harness.REPO / "native"), f"TARGET={lib}"],
                          stdout=subprocess.DEVNULL)
    return lib


def test_the_data_regenerate_byte_for_byte(tmp_path, native_lib):
    configs = tmp_path / "configs"
    shutil.copytree(BASE, configs / BASE.name)
    copy = configs / CDIR.name
    shutil.copytree(CDIR, copy)
    for made in MADE:
        (copy / made).unlink()
    shutil.rmtree(copy / "rules")
    subprocess.run(
        [sys.executable, "-m", "wafbench.tools.freeze_ingress", str(copy)],
        cwd=harness.REPO, check=True, capture_output=True, timeout=3600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", CKO_NATIVE_LIB=str(native_lib)))
    for made in MADE:
        assert (copy / made).read_bytes() == (CDIR / made).read_bytes(), made
    assert same_tree(copy / "rules", CDIR / "rules")
