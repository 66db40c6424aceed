"""The readers of the window stage record, each on a synthetic ``ctx``:
after - before of the cumulative ``stages`` block, ``sum_s`` over
``count``; None where the program reports no ``stages`` (the commit
before the record)."""

import pytest

from wafbench import harness

CELL = "crs-lite.ftw-salted-c1"
STAGE_MS = {  # per window, in the measured interval
    "queue_wait": 0.25, "depth_wait": 2.0, "route": 0.5, "assemble": 0.75,
    "tier_enqueue": 3.0, "prefilter_wait": 20.0, "prefilter_confirm": 25.0,
    "post_enqueue": 1.0, "inflight_wait": 0.125, "readback_wait": 2.5, "decode": 0.375,
    "resolve": 0.25, "loop_hop": 0.5, "reply_write": 1.5,
}
LANE_WAIT_MS, WALL_MS, REQS, WINDOWS = 1.0, 60.0, 6, 10


def block(windows, lanes=("interactive", "bulk")):
    """``windows`` windows in each lane, every one with STAGE_MS."""
    def series(n, ms):
        return {"count": n, "sum_s": n * ms / 1e3, "buckets": []}

    out = {"buckets_s": []}
    for stage, ms in STAGE_MS.items():
        out[stage] = {"aborted": 0, **{ln: series(windows, ms) for ln in lanes}}
    out["lane_wait"] = {"aborted": 3,
                        **{ln: series(windows * REQS, LANE_WAIT_MS) for ln in lanes}}
    out["window_wall"] = {"aborted": 0, **{ln: series(windows, WALL_MS) for ln in lanes}}
    return out


def ctx(before=7, after=7 + WINDOWS, trace=None):
    return {"before": {"stages": block(before)}, "after": {"stages": block(after)},
            "trace": trace or {"module_busy_s": {}, "module_runs": {}}}


WANT = {
    "lane_wait_ms_per_req": LANE_WAIT_MS,
    "dispatch_wait_ms_per_window": 0.25 + 2.0 + 0.125,
    "batcher_route_ms_per_window": 0.5 + 0.25,
    "assemble_ms_per_window": 0.75,
    "tier_enqueue_ms_per_window": 3.0 + 1.0,
    "prefilter_confirm_ms_per_window": 25.0,
    "prefilter_wait_ms_per_window": 20.0,
    "readback_wait_ms_per_window": 2.5,
    "decode_ms_per_window": 0.375,
    "reply_ms_per_window": 0.5 + 1.5,
    "window_unaccounted_share": 100.0 * (1 - (sum(STAGE_MS.values()) + LANE_WAIT_MS) / WALL_MS),
}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_stage_reader(metric):
    reader = harness.Cell(CELL).reader(metric)
    assert reader.SOURCE == "program_span"
    assert reader.read(ctx()) == pytest.approx(WANT[metric], rel=1e-9)
    # a program without the block (the parent commit): nothing to read, no raise
    assert reader.read({"before": {}, "after": {}, "trace": {}}) is None
    # no window ended in the interval
    assert reader.read(ctx(before=7, after=7)) is None


def test_prefilter_readers_find_nothing_without_a_prefiltered_group():
    c = ctx()
    for side in ("before", "after"):
        for stage in ("prefilter_wait", "prefilter_confirm"):
            del c[side]["stages"][stage]
    cell = harness.Cell(CELL)
    assert cell.reader("prefilter_wait_ms_per_window").read(c) is None
    assert cell.reader("prefilter_confirm_ms_per_window").read(c) is None
    assert cell.reader("decode_ms_per_window").read(c) == pytest.approx(0.375)


def test_post_device_ms_per_window():
    reader = harness.Cell(CELL).reader("post_device_ms_per_window")
    assert reader.SOURCE == "device_trace"
    trace = {"module_busy_s": {"jit_cko_eval_post_32x512(1)": 0.012, "jit_cko_match_32x512(2)": 0.3},
             "module_runs": {"jit_cko_eval_post_32x512(1)": 12, "jit_cko_match_32x512(2)": 12}}
    assert reader.read(ctx(trace=trace)) == pytest.approx(1.0)
    # the parent's names hold eval_post too; no post-stage run: nothing to read
    old = {"module_busy_s": {"jit_eval_post_tiered(9)": 0.006}, "module_runs": {"jit_eval_post_tiered(9)": 12}}
    assert reader.read(ctx(trace=old)) == pytest.approx(0.5)
    assert reader.read(ctx()) is None
    # and the matcher's reader still tells the two apart by that name
    matcher = harness.Cell(CELL).reader("matcher_device_ms_per_window")
    assert matcher.read(ctx(trace=trace)) == pytest.approx(25.0)


PREFILTER = {"prefilter_false_positive_share", "prefilter_confirm_ms_per_window",
             "prefilter_wait_ms_per_window"}


def test_the_cells_list_their_stage_metrics():
    """Every cell lists every stage reader; the prefilter's, the cells
    whose rule set has a prefiltered group (PR 32: the hand-read
    ``crs-bodies.api-2k-c1`` and ``sample.zipf-c2`` among them)."""
    bench = harness.Cell(CELL).bench
    listed = {w["name"]: {m["name"] for m in harness.Cell(w["name"]).metrics("per_layer")}
              for w in bench["workloads"]}
    for name, metrics in listed.items():
        crs = name.startswith("crs-")
        assert (set(WANT) | {"post_device_ms_per_window"}) - PREFILTER <= metrics, name
        assert (PREFILTER <= metrics) if crs else not (PREFILTER & metrics), name
    assert listed[CELL] - listed["sample.salted-c2"] == PREFILTER
    # the rolling medians since process start and the copy of the matcher's time are gone
    gone = {"batcher_host_stage_p50_ms", "native_window_p50_ms", "long_tier_device_ms_per_window"}
    assert not gone & {m["name"] for m in bench["per_layer"]}
    assert not [g for g in gone if (harness.REPO / "wafbench" / "layer_metrics" / f"{g}.py").exists()]
