"""The cells PR 28 adds, found by name; their generators' properties;
their controls; the readers they bring, on the recorded chip trace and
on hand-made ``/waf/v1/stats`` snapshots. The one whole run here (the
repeat cell and its control, on the CPU at the sample's size) starts
sidecars: slow, like ``test_served_path.py``."""

import json
import time
from collections import Counter

import pytest

from wafbench import harness
from wafbench.generators.planned_bursts import SALT_TOKEN

BODIES, ZIPF = "crs-bodies.api-2k-c1", "sample.zipf-c2"


def test_discovery_finds_the_bodies_cell():
    cell = harness.Cell(BODIES)
    assert cell.config["name"] == "crs-lite-pl2-bodies"
    assert cell.config_dir.name == "crs-lite-pl2-bodies"
    assert cell.config["reduced"] and cell.config["architecture"] is None
    assert cell.mix["generator"] == "planned_bursts" and cell.mix["plan"] == "api-2k"
    # the rule text is crs-lite-pl2's, byte for byte but for SecDataDir's path
    own = cell.rules_text().split("\n", 1)[1]
    assert own == harness.Cell("crs-lite.ftw-salted-c1").rules_text().split("\n", 1)[1]
    assert "SecRequestBodyAccess Off" in cell.rules_text(control=True)
    t = cell.traffic(2**31 + 28)
    assert len(t.connections) == 1 and len(t.connections[0]) == 40
    assert all(b.n == 6 and b.lane == "bulk" for b in t.connections[0])
    wire = t.salted(t.connections[0][0], "c0")
    assert SALT_TOKEN not in wire and wire.count(b"HTTP/1.1\r\n") == 6 and len(wire) <= 16384
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"tier_padding_share", "matcher_device_ms_per_window", "device_idle_share",
            "prefilter_wait_ms_per_window", "assemble_ms_per_window"} <= names
    assert "verdict_cache_hit_share" not in names
    assert "verdict_cache.hits_total" in cell.mix["zero_growth"]


def test_discovery_finds_the_repeat_cell():
    cell = harness.Cell(ZIPF)
    assert cell.config["name"] == "operator-sample" and cell.mix["generator"] == "zipf_repeat"
    assert not any(k.startswith("verdict_cache.") for k in cell.mix["zero_growth"])
    salted = harness.Cell("sample.salted-c2").mix["zero_growth"]
    assert cell.mix["zero_growth"] == [k for k in salted if not k.startswith("verdict_cache.")]
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert "verdict_cache_hit_share" in names and "tier_padding_share" not in names


def test_repeat_bursts_hold_a_fixed_pool_and_sixteen_salted():
    cell = harness.Cell(ZIPF)
    a, b = cell.traffic(2**31 + 5), cell.traffic(7)
    assert [x.n for x in a.prime] == [128, 128]  # each lane's repeat pool, whole, once
    assert len(a.connections) == 2 and [len(c) for c in a.connections] == [16, 16]
    fixed = {parts[0] for burst in a.prime for parts in burst.parts}
    assert len(fixed) == 256 and not any(SALT_TOKEN in w for w in fixed)
    draws = Counter()
    for traffic in (a, b):
        for conn in (0, 1):
            stream = traffic.stream(conn)
            for _ in range(20):
                burst = next(stream)
                assert burst.n == 128
                unsalted = [p[0] for p in burst.parts if len(p) == 1]
                assert len(unsalted) == 112 and set(unsalted) <= fixed
                draws.update(unsalted)
                w1, w2 = traffic.salted(burst, "c"), traffic.salted(burst, "c")
                assert w1 != w2 and all(u in w1 and u in w2 for u in unsalted)
    # Zipf: each lane's first pool request is drawn most
    top = [w for w, _n in draws.most_common(2)]
    assert set(top) == {a.prime[0].parts[0][0], a.prime[1].parts[0][0]}
    # the same seed sends the same bytes; every seed the same salted groups
    c = cell.traffic(7)
    sb, sc = b.stream(0), c.stream(0)
    assert [b.salted(next(sb), "c0") for _ in range(3)] == [c.salted(next(sc), "c0") for _ in range(3)]
    groups = lambda t: sorted(tuple(map(tuple, (p for p, _e in g))) for _lane, g in t.connections[0])
    assert groups(a) == groups(b)


def test_bodies_control_changes_the_reference_verdicts():
    """Body inspection off is no cheaper way to the same answers: the
    plain host evaluator on the control's rule text differs from
    ``corpus.jsonl`` on every request whose attack sits in its body."""
    import base64
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from wafbench.tools.freeze_bodies import materialize

    cell = harness.Cell(BODIES)
    engine = WafEngine(cell.rules_text(control=True))
    rows = [json.loads(line) for line in open(cell.config_dir / "corpus.jsonl")]
    reqs = [materialize(base64.b64decode(r["wire"]), b"ab" * 16) for r in rows]
    got = [(v.status if v.interrupted else 200, str(v.rule_id or 0) if v.interrupted else None)
           for v in engine.host_fallback.evaluate(reqs)]
    differ = sum(g != (r["status"], r["rule_id"]) for g, r in zip(got, rows))
    blocked = sum(r["status"] != 200 for r in rows)
    assert blocked >= 36 and differ >= blocked * 0.9


def in_process(workload, **kw):
    rc, result = harness.run_cell(workload, seed=2**31 + 28, seconds=3.0, trace=False,
                                  t_process_start=time.monotonic(), rehearse_cpu=True,
                                  device_check=False, **kw)
    assert rc == 0
    return result


def test_repeat_cell_is_correct_on_the_cpu_and_its_control_is_not(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    sound = in_process(ZIPF)
    assert sound["correct"] is True and sound["failed_checks"] == []
    control = in_process(ZIPF, control=True)
    assert control["correct"] is False and "verdicts_that_differ" in control["failed_checks"]


# -- readers -----------------------------------------------------------------------


def stats(**blocks):
    return {"tiering": {"windows": 0, "tiers": 0, "cells": 0, "real_bytes": 0},
            "verdict_cache": {"hits_total": 0, "window_dedup_rows": 0}, **blocks}


def test_tier_padding_share():
    reader = harness.Cell(BODIES).reader("tier_padding_share")
    assert reader.SOURCE == "program_counter"
    before = stats(tiering={"windows": 5, "tiers": 5, "cells": 5 * 65536, "real_bytes": 40000})
    after = stats(tiering={"windows": 15, "tiers": 15, "cells": 15 * 65536, "real_bytes": 121920})
    assert reader.read({"before": before, "after": after}) == pytest.approx(
        100 * (1 - 81920 / 655360))
    assert reader.read({"before": before, "after": before}) is None  # no tier launched
    assert reader.read({"before": {}, "after": {}}) is None  # the parent: no block, no raise


def test_verdict_cache_hit_share():
    reader = harness.Cell(ZIPF).reader("verdict_cache_hit_share")
    assert reader.SOURCE == "program_counter"
    before = stats(verdict_cache={"hits_total": 100, "window_dedup_rows": 4})
    after = stats(verdict_cache={"hits_total": 1200, "window_dedup_rows": 24})
    ctx = {"before": before, "after": after, "attempted": 1280}
    assert reader.read(ctx) == pytest.approx(100 * 1120 / 1280)
    assert reader.read({"before": {}, "after": {}, "attempted": 10}) is None
    assert reader.read({**ctx, "attempted": 0}) is None
