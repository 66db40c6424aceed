"""The matcher's device time a window is read over the capture's whole
windows (PR 39): a capture that cuts a window at either edge reads what
one that cuts none does, the reduction keeps the order of the runs, and
a reduction without it (an older ``trace_reduced.json``) reads as before.
"""

import pytest

from wafbench import harness, trace_reduce
from wafbench.layer_metrics._trace_windows import whole_windows

MATCH, POST = "jit_cko_match_32x512(2)", "jit_cko_eval_post_32x512(1)"
MATCH_S, POST_S, WINDOW_S = 0.009, 0.00001, 0.015


def capture(windows: int, orphan_post: bool = False, trailing_match: bool = False) -> dict:
    """What ``reduce`` returns for ``windows`` whole windows, with a post
    stage whose matcher ran before the capture began and/or a matcher
    whose post stage ran after it ended."""
    runs, t = [], 0.0
    if orphan_post:
        runs.append([POST, t, POST_S])
        t += WINDOW_S - MATCH_S
    for _ in range(windows):
        runs += [[MATCH, t, MATCH_S], [POST, t + MATCH_S + 0.001, POST_S]]
        t += WINDOW_S
    if trailing_match:
        runs.append([MATCH, t, MATCH_S])
    names = [r[0] for r in runs]
    return {"module_events": [runs],
            "module_busy_s": {MATCH: MATCH_S * names.count(MATCH), POST: POST_S * names.count(POST)},
            "module_runs": {MATCH: names.count(MATCH), POST: names.count(POST)}}


@pytest.mark.parametrize("orphan_post", [False, True])
@pytest.mark.parametrize("trailing_match", [False, True])
@pytest.mark.parametrize("windows", [21, 28, 77])
def test_a_window_cut_at_an_edge_does_not_move_the_matchers_time(windows, orphan_post, trailing_match):
    trace = capture(windows, orphan_post, trailing_match)
    seconds, whole = whole_windows(trace)
    assert whole == windows - 1 + orphan_post  # from the first post stage's end to the last's
    assert seconds / whole == pytest.approx(MATCH_S, rel=1e-9)
    cell = harness.Cell("crs-custom5k.ftw-salted-c1")
    c = {"trace": trace, "setup": {"automata": {"rules": 5269}}}
    assert cell.reader("matcher_device_ms_per_window").read(c) == pytest.approx(9.0, rel=1e-9)
    assert cell.reader("matcher_device_ms_per_kilorule").read(c) == pytest.approx(9.0 / 5.269, rel=1e-9)
    # over the whole capture, as before PR 39, the cut windows show: one part in the windows
    old = 1e3 * trace["module_busy_s"][MATCH] / trace["module_runs"][POST]
    assert (old == pytest.approx(9.0)) == (orphan_post == trailing_match)


def test_two_windows_in_flight_one_run_off_a_whole_number_is_scaled_to_it():
    """Lanes A and B interleave on the device's queue (M_a M_b P_a M_a P_b ...):
    the last post stage's own matcher ran before the first edge here, so 27
    windows hold 26 matcher runs between the edges."""
    runs, t = [[MATCH, 0.0, MATCH_S], [MATCH, 0.010, MATCH_S]], 0.020
    for _ in range(27):
        runs += [[POST, t, POST_S], [MATCH, t + 0.001, MATCH_S]]
        t += 0.011
    runs[-1:] = [[POST, t, POST_S]]  # the capture ends on two post stages in a row
    seconds, windows = whole_windows({"module_events": [runs]})
    between = [r for r in runs if r[0] == MATCH and r[1] >= 0.020 + POST_S]
    assert (len(between), windows) == (26, 27)
    assert seconds / windows == pytest.approx(MATCH_S, rel=1e-9)
    # two tiers a window in every window is a whole number too, and is left as it is
    two = capture(10)
    two["module_events"][0] = [r for run in two["module_events"][0]
                               for r in ([run, [MATCH, run[1] + 0.0001, 0.0]] if run[0] == MATCH else [run])]
    assert whole_windows(two) == (pytest.approx(9 * MATCH_S), 9)


def test_without_the_order_of_runs_the_whole_capture_is_read():
    trace = capture(12, trailing_match=True)
    del trace["module_events"]
    seconds, windows = whole_windows(trace)
    assert windows == 12 and seconds == pytest.approx(13 * MATCH_S)
    one_post = capture(1)
    assert whole_windows(one_post) == (pytest.approx(MATCH_S), 1)  # fewer than two post stages
    assert whole_windows({"module_events": [], "module_busy_s": {}, "module_runs": {}}) == (0.0, 0)
    cell = harness.Cell("crs-lite.ftw-salted-c1")
    assert cell.reader("matcher_device_ms_per_window").read(
        {"trace": {"module_busy_s": {}, "module_runs": {}}}) is None


def test_the_reduction_keeps_every_executable_run_in_the_order_it_ran():
    ms = 1_000_000
    modules = [["jit_post(2)", 20 * ms, 5 * ms], ["jit_match(1)", 2 * ms, 10 * ms],
               ["jit_match(1)", 40 * ms, 2 * ms]]
    r = trace_reduce.reduce({"devices": [{"name": "/device:TPU:0", "ops": [["fusion.1", 2 * ms, ms]],
                                          "modules": modules}],
                             "host": [["python3#0", "$x.py:1 f", 0, 50 * ms]]})
    assert r["module_events"] == [[["jit_match(1)", pytest.approx(0.002), pytest.approx(0.010)],
                                   ["jit_post(2)", pytest.approx(0.020), pytest.approx(0.005)],
                                   ["jit_match(1)", pytest.approx(0.040), pytest.approx(0.002)]]]
    assert trace_reduce.reduce({"devices": [], "host": []})["module_events"] == []
