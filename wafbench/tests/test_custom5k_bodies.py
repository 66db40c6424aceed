"""The site's feed in front of an API (PR 43): ``crs-lite-pl2-custom5k-bodies``
and its cell ``crs-custom5k-bodies.api-2k-c1``, found by name. The rule
text is ``crs-lite-pl2-custom5k``'s byte for byte; the pool is
``crs-lite-pl2-bodies``' 240 bodied requests and 48 custom bodied ones,
counted by content type, template and carrier; every steady burst is one
``32x2048`` window that sends six of the pool and one custom request,
blocked and near-miss alternating, and a cycle of 48 bursts sends the
pool's 144 twice and every custom request once; the control (the feed removed) differs
from the reference on exactly the 24 a feed rule decides; the two readers
the cell brings, on hand-made ``/waf/v1/stats`` snapshots. All JAX-free
but the control's (the plain host evaluator on crs-lite's 269 rules) and
the last, slow one: the data regenerate byte for byte from
``freeze_custom_bodies`` (an engine on 5,269 rules and four passes of the
host evaluator; run with ``pytest wafbench/tests``). Tier-1 imports the
others through ``tests/test_wafbench_custom5k_bodies.py``.
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
from wafbench.tools import freeze_custom, freeze_custom_bodies

CELL = "crs-custom5k-bodies.api-2k-c1"
BENCH = Path(harness.__file__).resolve().parent
CDIR = BENCH / "configs" / "crs-lite-pl2-custom5k-bodies"
MADE = ("corpus.jsonl", "frozen.json", "plans/api-custom-2k.json",
        f"rules/{freeze_custom.FEED_FILE}")
CTYPES = freeze_custom_bodies.CTYPES


def pool() -> list[dict]:
    return [json.loads(line) for line in open(CDIR / "corpus.jsonl")]


def plan() -> dict:
    return json.loads((CDIR / "plans" / "api-custom-2k.json").read_text())


def content_type(row: dict) -> str:
    head = base64.b64decode(row["wire"]).partition(b"\r\n\r\n")[0].decode("latin-1")
    return next(k for k, v in CTYPES.items() if f"Content-Type: {v}" in head)


def same_tree(a: Path, b: Path) -> bool:
    cmp = filecmp.dircmp(a, b)
    if cmp.left_only or cmp.right_only or cmp.funny_files:
        return False
    _same, differ, errors = filecmp.cmpfiles(a, b, cmp.common_files, shallow=False)
    return not differ and not errors and all(same_tree(a / d, b / d) for d in cmp.common_dirs)


def test_the_cell_resolves_and_states_its_deployment():
    cell = harness.Cell(CELL)
    assert cell.config["name"] == cell.config_dir.name == "crs-lite-pl2-custom5k-bodies"
    assert cell.config["architecture"] is None and "sidecar_args" not in cell.config
    entry = next(c for c in cell.bench["configs"] if c["name"] == cell.config["name"])
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cell.config["reduced"] == list(cell.config["reduced_why"]) == [
        "body_bytes_tail_131072_cut_to_2048_by_the_360s_run_limit",
        "batch_64k_rows_cut_to_one_32x2048_window_a_burst_one_shape_a_run"]
    assert cell.workload["chips"] == 1 and cell.workload["traffic"] == "api-custom-2k-c1"
    bodies = harness.Cell("crs-bodies.api-2k-c1")
    assert cell.mix["zero_growth"] == bodies.mix["zero_growth"]
    assert cell.mix["connections"] == bodies.mix["connections"] == [{"lanes": ["bulk"]}]
    assert cell.mix["generator"] == "planned_bursts" and cell.mix["plan"] == "api-custom-2k"
    assert cell.config["deployment"]["body_processors"] == \
        bodies.config["deployment"]["body_processors"]
    assert set(bodies.config["guarantees"]) <= set(cell.config["guarantees"])
    assert "names the feed rule that blocked it" in cell.config["guarantees"]["verdict"]
    # the argv is crs-bodies': the harness's own five and nothing else
    assert cell.sidecar_argv(1, 2, None) == bodies.sidecar_argv(1, 2, None)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"seg_conv_steps_per_launch", "seg_long_scan_launch_share",
            "matcher_device_ms_per_window", "device_idle_share", "assemble_ms_per_window"} <= names
    for other in ("crs-bodies.api-2k-c1", "crs-custom5k.ftw-salted-c1"):
        theirs = {m["name"] for m in harness.Cell(other).metrics("per_layer")}
        assert not {"seg_conv_steps_per_launch", "seg_long_scan_launch_share"} & theirs
    control = cell.rules_text(control=True)
    assert control.endswith("\nSecRuleRemoveById 9000000-9004999\n")
    assert control.startswith(cell.rules_text())


def test_the_rule_text_is_custom5ks_byte_for_byte():
    assert same_tree(CDIR / "rules", BENCH / "configs" / "crs-lite-pl2-custom5k" / "rules")
    assert harness.Cell(CELL).rules_text().split("\n", 1)[1] == \
        harness.Cell("crs-custom5k.ftw-salted-c1").rules_text().split("\n", 1)[1]
    feed = freeze_custom.feed_rules(5000, 37)
    assert (CDIR / "rules" / freeze_custom.FEED_FILE).read_text() == freeze_custom.feed_text(feed)


def test_the_pool_is_the_bodied_pool_and_48_custom_bodied_requests():
    rows = pool()
    base, custom = rows[:240], rows[240:]
    theirs = [json.loads(line) for line in
              open(BENCH / "configs" / "crs-lite-pl2-bodies" / "corpus.jsonl")]
    # the same requests under the same verdicts: the feed moved none
    assert [(r["id"], r["wire"], r["status"], r["rule_id"]) for r in base] == \
        [(r["id"], r["wire"], r["status"], r["rule_id"]) for r in theirs]
    assert Counter(content_type(r) for r in base) == {"json": 133, "urlencoded": 65,
                                                      "multipart": 42}
    assert len(custom) == 48
    hits = [r for r in custom if r["id"].endswith("-hit")]
    nears = [r for r in custom if r["id"].endswith("-near")]
    assert len(hits) == len(nears) == 24
    assert all(r["status"] == 403 and r["id"] == f"custom-{r['rule_id']}-hit" for r in hits)
    assert all(r["status"] == 200 and r["rule_id"] is None for r in nears)
    rules = sorted(int(r["rule_id"]) for r in hits)
    assert len(set(rules)) == 24 and rules[0] == 9000000 and rules[-1] == 9004999
    assert rules == [9000000 + i for i in freeze_custom_bodies.picks(5000)]
    by_template = Counter(freeze_custom.TEMPLATE_OF[(r - 9000000) % 10] for r in rules)
    assert by_template == {"a": 5, "b": 10, "c": 4, "d": 5}
    feed = {r["id"]: r for r in freeze_custom.feed_rules(5000, 37)}
    carriers, long_pairs = Counter(), 0
    for hit, near in zip(hits, nears):
        rule = feed[int(hit["rule_id"])]
        w_hit, w_near = base64.b64decode(hit["wire"]), base64.b64decode(near["wire"])
        assert near["id"] == hit["id"].replace("-hit", "-near")
        # one byte apart: the last byte of the rule's last token
        assert len(w_hit) == len(w_near) and sum(a != b for a, b in zip(w_hit, w_near)) == 1
        head, _, body = w_hit.partition(b"\r\n\r\n")
        assert head.split(b" ", 1)[0] in (b"POST", b"PUT", b"PATCH") and body
        assert SALT_TOKEN in body and b"X-Request-Id: rq-" + SALT_TOKEN in head
        last = rule["tokens"][-1].encode()
        where = {"a": head.split(b"\r\n")[0], "c": head, "b": body, "d": body}[rule["template"]]
        assert last in where and last not in {"a": body, "c": body, "b": head,
                                              "d": head}[rule["template"]]
        carriers[{"a": "uri", "c": "user-agent"}.get(rule["template"],
                                                     content_type(hit) + " field")] += 1
        long_pairs += len(body) - len(SALT_TOKEN) + 32 > 1024
    assert carriers == {"json field": 8, "urlencoded field": 5, "multipart field": 2,
                        "uri": 5, "user-agent": 4}
    assert long_pairs >= 8
    frozen = json.loads((CDIR / "frozen.json").read_text())
    assert frozen["moved_by_feed"] == [] and frozen["rules_compiled"] == 5269
    assert frozen["custom_blocked_by"] == rules and len(frozen["salt_seeds"]) == 4
    assert frozen["custom_pairs"]["by_carrier"] == dict(carriers)
    assert frozen["automata_summary"]["segment_columns"] == 12498


def test_every_burst_is_one_32x2048_window_and_a_pass_sends_every_custom_request_once():
    p, rows = plan(), pool()
    assert p["tier_shapes"] == [[32, 2048]]
    steady = p["steady"]
    assert len(steady) == 48 and p["requests_per_pass"] == 48 * 7
    sent = Counter()
    for k, b in enumerate(steady):
        assert b["lane"] == "bulk" and b["tier_shapes"] == [[32, 2048]]
        assert b["unique_uncached_rows"] <= 32 and b["wire_bytes"] <= 65536
        base, (custom,) = b["requests"][:6], b["requests"][6:]
        # six of the pool, then one custom request: a rule's blocked one, then its near-miss
        assert len(b["requests"]) == 7 and all(i < 240 for i in base) and custom >= 240
        assert rows[custom]["status"] == (200 if k % 2 else 403)
        assert rows[custom]["id"].endswith("-near" if k % 2 else "-hit")
        assert base == steady[k % 24]["requests"][:6]  # the 24 groups, then again
        longs = sum(len(base64.b64decode(rows[i]["wire"]).partition(b"\r\n\r\n")[2])
                    - len(SALT_TOKEN) + 32 > 1024 for i in base)
        assert 1 <= longs <= 2
        sent.update(b["requests"])
    # 144 of the pool twice a cycle and all 48 custom requests once
    assert {sent[i] for i in sent if i < 240} == {2} and {sent[i] for i in sent if i >= 240} == {1}
    assert sum(i < 240 for i in sent) == 144 and sum(i >= 240 for i in sent) == 48
    assert len({str(b["post_shapes"]) for b in steady}) == 1  # one post stage for all
    # the prime pass: what the steady bursts send and nothing else, each group one window
    prime = p["prime"]
    spec = json.loads((CDIR / "freeze.json").read_text())
    assert len(prime) <= spec["prime_groups_max"]
    assert all(b["tier_shapes"] == [[32, 2048]] and b["unique_uncached_rows"] <= 32
               and b["lane"] == "bulk" for b in prime)
    assert {i for b in prime for i in b["requests"]} == set(sent)
    assert prime[-1]["requests"] == steady[0]["requests"]  # mints the steady post stage
    cell = harness.Cell(CELL)
    t = cell.traffic(2**31 + 43)
    assert len(t.connections) == 1 and len(t.connections[0]) == 48
    assert all(b.n == 7 and b.lane == "bulk" for b in t.connections[0])
    wire = t.salted(t.connections[0][0], "c0")
    assert SALT_TOKEN not in wire and wire.count(b"HTTP/1.1\r\n") == 7


def test_the_control_differs_on_exactly_the_24_a_feed_rule_decides():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from wafbench.tools.freeze_bodies import materialize

    cell = harness.Cell(CELL)
    engine = WafEngine(cell.rules_text(control=True))
    rows = pool()
    sent = sorted({i for b in plan()["steady"] for i in b["requests"]})
    reqs = [materialize(base64.b64decode(rows[i]["wire"]), b"ab" * 16) for i in sent]
    got = [(v.status if v.interrupted else 200, str(v.rule_id or 0) if v.interrupted else None)
           for v in engine.host_fallback.evaluate(reqs)]
    differ = [rows[i]["id"] for i, g in zip(sent, got)
              if g != (rows[i]["status"], rows[i]["rule_id"])]
    assert len(differ) == 24 and all(d.endswith("-hit") for d in differ)


# -- the readers the cell brings ----------------------------------------------------------


def plan_of(path, chunks, tiles):
    return {"path": path, "row_chunks": chunks, "rows_per_chunk": 32 // max(1, chunks),
            "column_tiles": tiles, "columns_per_tile_max": 2046, "columns": 12498}


def test_seg_conv_steps_per_launch():
    reader = harness.Cell(CELL).reader("seg_conv_steps_per_launch")
    assert reader.SOURCE == "program_counter"
    listed = [
        {"name": "cko_match_32x2048", "model": "aa", "seg_plan": plan_of("tiles", 1, 13)},
        {"name": "cko_match_32x512", "model": "aa", "seg_plan": plan_of("rows", 2, 8)},
        {"name": "cko_match_16x32", "model": "aa", "seg_plan": plan_of("direct", 1, 8)},  # never ran
        {"name": "cko_eval_post_32x2048", "model": "aa", "seg_plan": None},
    ]
    runs = {"jit_cko_match_32x2048(7)": 30, "jit_cko_match_32x512(9)": 10,
            "jit_cko_eval_post_32x2048(3)": 40}
    ctx = {"setup": {"compile_cache": {"executables": listed}}, "trace": {"module_runs": runs}}
    assert reader.read(ctx) == pytest.approx((30 * 13 + 10 * 16) / 40)
    # an executable on the long scan is left out: the scan never scores best
    long = [dict(listed[0], seg_plan=plan_of("long", 1, 0)), *listed[1:]]
    assert reader.read({"setup": {"compile_cache": {"executables": long}},
                        "trace": {"module_runs": runs}}) == 16.0
    assert reader.read({"setup": {"compile_cache": {"executables": [long[0], listed[3]]}},
                        "trace": {"module_runs": runs}}) is None
    # the parent: executables listed without a plan, or no list at all: nothing, no raise
    bare = [{"name": e["name"], "model": "aa", "device_ops": None} for e in listed]
    assert reader.read({"setup": {"compile_cache": {"executables": bare}},
                        "trace": {"module_runs": runs}}) is None
    assert reader.read({"setup": {"compile_cache": {"entries": 3}},
                        "trace": {"module_runs": runs}}) is None
    assert reader.read({"setup": {}, "trace": {"module_runs": runs}}) is None
    assert reader.read({"setup": {"compile_cache": {"executables": listed}},
                        "trace": {"module_runs": {}}}) is None


def test_seg_long_scan_launch_share():
    reader = harness.Cell(CELL).reader("seg_long_scan_launch_share")
    assert reader.SOURCE == "program_counter"

    def stats(long, windows, **tiering):
        return {"tiering": {"windows": windows, "long_scan_launches": long, **tiering},
                "compile_cache": {"device_windows": windows}}

    assert reader.read({"before": stats(0, 100), "after": stats(0, 150)}) == 0.0
    assert reader.read({"before": stats(100, 100), "after": stats(150, 150)}) == 100.0
    assert reader.read({"before": stats(4, 100), "after": stats(14, 140)}) == pytest.approx(25.0)
    assert reader.read({"before": stats(0, 100), "after": stats(0, 100)}) is None  # no window
    parent = {"tiering": {"windows": 5, "tiers": 5}, "compile_cache": {"device_windows": 5}}
    assert reader.read({"before": parent, "after": parent}) is None  # no counter, no raise
    assert reader.read({"before": {}, "after": {}}) is None


# -- slow: the data regenerate ----------------------------------------------------------------


@pytest.fixture(scope="module")
def native_lib(tmp_path_factory):
    lib = tmp_path_factory.mktemp("native") / "libcko_native.so"
    subprocess.check_call(["make", "-C", str(harness.REPO / "native"), f"TARGET={lib}"],
                          stdout=subprocess.DEVNULL)
    return lib


def test_the_data_regenerate_byte_for_byte(tmp_path, native_lib):
    configs = tmp_path / "configs"
    for base in ("crs-lite-pl2", "crs-lite-pl2-bodies"):
        shutil.copytree(BENCH / "configs" / base, configs / base)
    copy = configs / CDIR.name
    shutil.copytree(CDIR, copy)
    for made in MADE:
        (copy / made).unlink()
    subprocess.run(
        [sys.executable, "-m", "wafbench.tools.freeze_custom_bodies", str(copy)],
        cwd=harness.REPO, check=True, capture_output=True, timeout=3600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", CKO_NATIVE_LIB=str(native_lib)))
    for made in MADE:
        assert (copy / made).read_bytes() == (CDIR / made).read_bytes(), made
    assert same_tree(copy / "rules", CDIR / "rules")
