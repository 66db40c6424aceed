"""The three readers of the executables' operation counts (PR 41):
``matcher_device_ops_per_launch``, ``matcher_chain_ops_share`` and
``matcher_unscoped_ops_share`` weigh the matcher executables the capture
ran by their runs, leave the post stage out, and give nothing on a
program that keeps no counts (the parent of the PR that brought them)."""

import json

import pytest

from wafbench import harness

CELL = "crs-lite.ftw-salted-c1"
READERS = ("matcher_device_ops_per_launch", "matcher_chain_ops_share",
           "matcher_unscoped_ops_share")


def ops(total, bucket=0, suffix=0, final=0, conv=0, unscoped=0):
    by = {"cko.seg.bucket": bucket, "cko.seg.suffix": suffix, "cko.seg.final": final,
          "cko.seg.conv": conv}
    return {"total": total, "by_scope": {k: v for k, v in by.items() if v}, "unscoped": unscoped}


def ctx(executables, runs):
    return {"setup": {"compile_cache": {"executables": executables} if executables is not None
                      else {"entries": 3}},
            "trace": {"module_runs": runs}}


TWO_SHAPES = [
    {"name": "cko_match_32x512", "model": "aa", "device_ops": ops(1000, 50, 400, 150, 20, 30)},
    {"name": "cko_match_32x64", "model": "aa", "device_ops": ops(400, 10, 100, 50, 20, 10)},
    {"name": "cko_match_16x32", "model": "aa", "device_ops": ops(90000, unscoped=90000)},  # never ran
    {"name": "cko_eval_post_32x512_32x64", "model": "aa", "device_ops": ops(7000, unscoped=7000)},
]
RUNS = {"jit_cko_match_32x512(7)": 30, "jit_cko_match_32x64(9)": 10,
        "jit_cko_eval_post_32x512_32x64(3)": 30}


def read(name, c):
    return harness.Cell(CELL).reader(name).read(c)


def test_two_matcher_shapes_are_weighed_by_their_runs_and_the_post_stage_is_left_out():
    c = ctx(TWO_SHAPES, RUNS)
    assert read("matcher_device_ops_per_launch", c) == pytest.approx((30 * 1000 + 10 * 400) / 40)
    assert read("matcher_chain_ops_share", c) == pytest.approx(
        100.0 * (30 * 600 + 10 * 160) / (30 * 1000 + 10 * 400))
    assert read("matcher_unscoped_ops_share", c) == pytest.approx(
        100.0 * (30 * 30 + 10 * 10) / (30 * 1000 + 10 * 400))


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_block_gives_nothing(name):
    assert read(name, ctx(None, RUNS)) is None


@pytest.mark.parametrize("name", READERS)
def test_an_executable_that_was_not_counted_is_left_out_and_none_counted_gives_nothing(name):
    uncounted = [dict(e, device_ops=None) if e["name"] == "cko_match_32x64" else e
                 for e in TWO_SHAPES]
    only_wide = read(name, ctx(uncounted, RUNS))
    assert only_wide == read(name, ctx(TWO_SHAPES, {"jit_cko_match_32x512(7)": 30}))
    assert read(name, ctx([dict(e, device_ops=None) for e in TWO_SHAPES], RUNS)) is None
    assert read(name, ctx(TWO_SHAPES, {"jit_cko_eval_post_32x512_32x64(3)": 30})) is None


def test_the_chain_scopes_are_names_the_program_registers():
    """The reader's three names are the program's (the benchmark imports
    nothing of it: a rename there would silently read 0 here)."""
    from coraza_kubernetes_operator_tpu.observability.device_scopes import SCOPES
    from wafbench.layer_metrics._device_ops import CHAIN_SCOPES

    assert set(CHAIN_SCOPES) <= set(SCOPES)


def test_the_three_are_listed_for_the_five_crs_cells_and_no_other():
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    crs = {w["name"] for w in bench["workloads"] if w["config"].startswith("crs-lite-pl2")}
    assert len(crs) == 5
    for m in bench["per_layer"][-3:]:
        assert m["name"] in READERS and set(m["workloads"]) == crs
        assert (m["source"], m["moves"]) == ("program_counter", "latency_p50_ms")
    for w in bench["workloads"]:
        names = {m["name"] for m in harness.Cell(w["name"]).metrics("per_layer")}
        assert (set(READERS) <= names) == (w["name"] in crs), w["name"]
