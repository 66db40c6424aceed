"""Lazy per-tier compilation (cold-compile collapse).

Two contracts from the split dispatch:

1. **Parity** — a lazily-compiled engine (tiers still routing through
   the host fallback because no executable has landed) returns verdicts
   BIT-IDENTICAL to the eager engine, on attack traffic drawn from the
   go-ftw crs-lite corpus; and once the executables land, the same
   engine serves from device with the same verdicts.
2. **Smallest-first** — pending compiles are submitted in ascending
   cost order with the post stage first, so first-verdict latency after
   a cold start is gated on the smallest tier's compile, not the sum.
"""

from __future__ import annotations

from pathlib import Path

from coraza_kubernetes_operator_tpu.corpus import sample_rules
from coraza_kubernetes_operator_tpu.engine import tier_compile
from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE
from coraza_kubernetes_operator_tpu.engine.request import HttpRequest
from coraza_kubernetes_operator_tpu.engine.tier_compile import TierCompiler
from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
from coraza_kubernetes_operator_tpu.ftw.loader import load_tests
from coraza_kubernetes_operator_tpu.ftw.runner import _stage_request

FTW_DIR = Path(__file__).resolve().parents[1] / "ftw" / "tests-crs-lite"


def _ftw_attack_requests(limit: int = 48) -> list[HttpRequest]:
    """Request-phase stages from the crs-lite go-ftw corpus, sampled
    across rule families (every kth stage) so SQLi/XSS payloads are
    represented, not just the first file's protocol probes."""
    reqs = []
    for test in load_tests(FTW_DIR):
        for stage in test.stages:
            if stage.response_status is not None:
                continue  # response-phase stages need an upstream
            reqs.append(_stage_request(stage))
    return reqs[:: max(1, len(reqs) // limit)][:limit]


def _vt(v):
    return (v.interrupted, v.status, v.rule_id, v.matched_ids, v.scores)


def test_lazy_host_routing_matches_eager_on_ftw_corpus(monkeypatch):
    reqs = _ftw_attack_requests()
    assert len(reqs) >= 24

    eager = WafEngine(sample_rules())
    eager_first = [_vt(v) for v in eager.evaluate(reqs)]
    # Second pass = steady state: the value cache now feeds the post
    # stage cached rows, which is its own executable signature.
    eager_second = [_vt(v) for v in eager.evaluate(reqs)]
    assert any(t[0] for t in eager_first), "corpus sample matched nothing"

    monkeypatch.setenv("CKO_LAZY_TIERS", "1")
    lazy = WafEngine(sample_rules())
    assert lazy._lazy

    # Cold start, nothing resident yet: force every residency probe to
    # miss so EVERY stage routes through the host twin.
    with monkeypatch.context() as m:
        m.setattr(TierCompiler, "resident", lambda self, spec, key=None: False)
        m.setattr(TierCompiler, "ensure", lambda self, spec, key=None: False)
        lazy_cold = [_vt(v) for v in lazy.evaluate(reqs)]
    assert lazy_cold == eager_first
    assert not lazy.warmed, "host-served window must not claim warmed"

    # The executables exist now (the eager engine minted them; same
    # shapes => same keys): the SAME engine promotes to device serving
    # and the verdicts do not move.
    lazy_warm = [_vt(v) for v in lazy.evaluate(reqs)]
    assert lazy_warm == eager_second
    assert lazy.warmed, "resident executables should serve from device"

    # Metrics surface: the engine reports its distinct executable
    # signatures (>= one matcher + the post stage).
    assert lazy.compiled.report.exec_signatures >= 2


def test_lazy_cold_dispatch_enqueues_compiles(monkeypatch):
    """With nothing resident, the lazy path must still ENQUEUE every
    stage's compile (ensure == submit) while serving from host."""
    monkeypatch.setenv("CKO_LAZY_TIERS", "1")
    submitted = []
    monkeypatch.setattr(
        TierCompiler,
        "ensure",
        lambda self, spec, key=None: (submitted.append(spec[0]), False)[1],
    )
    eng = WafEngine(
        "SecRuleEngine On\n"
        'SecRule ARGS "@rx lazy-tier-probe-[0-9]+" '
        '"id:900,phase:2,deny,status:403"\n'
    )
    verdicts = eng.evaluate(
        [
            HttpRequest(uri="/?q=lazy-tier-probe-7"),
            HttpRequest(uri="/?q=benign"),
        ]
    )
    assert [v.interrupted for v in verdicts] == [True, False]
    assert "post" in submitted
    assert any(lbl.startswith("match:") for lbl in submitted)
    # Submission order is ascending cost: post (cost 0) leads.
    assert submitted[0] == "post"


class _RecordingCache:
    """Stand-in for EXEC_CACHE with an empty residency set: records the
    order compiles EXECUTE (single worker => submission order)."""

    def __init__(self):
        self.warm_order: list[str] = []

    def _lookup(self, key, count_hit=False):
        return None

    def warm(self, jitted, args, statics, key=None):
        self.warm_order.append(getattr(jitted, "__name__", "?"))
        return True


def test_compile_order_is_smallest_first(monkeypatch):
    """First-verdict gating: on a cold multi-tier batch, the post stage
    compiles first and matcher tiers follow in ascending rows*width."""
    eng = WafEngine(sample_rules())
    # Mixed value lengths land in two length tiers. Each side needs
    # >= _MIN_TIER_ROWS rows or the tier merge collapses the lattice
    # back to one executable (exactly what small batches should do).
    reqs = [HttpRequest(uri=f"/?a=short-{i}") for i in range(300)]
    reqs += [
        HttpRequest(uri=f"/?b={i}-" + "A" * 700) for i in range(300)
    ]
    tiers, numvals, _masks, cached, _mk, lease = eng._batch_tensors(reqs)
    if lease is not None:
        lease.release()  # only shapes are read below; no dispatch
    match_specs, post_spec, _pairs = eng._tier_specs(
        tiers, numvals, cached=cached
    )
    assert len(match_specs) >= 2, "expected a multi-tier batch"

    stub = _RecordingCache()
    monkeypatch.setattr(tier_compile, "EXEC_CACHE", stub)
    tc = TierCompiler(workers=1)
    minted = tc.compile_all(match_specs + [post_spec])

    assert minted == len(match_specs) + 1
    costs = [c for _lbl, c in tc.submitted]
    assert costs == sorted(costs), tc.submitted
    assert tc.submitted[0][0] == "post"
    # With one worker, execution order == submission order: the post
    # executable is minted before any matcher.
    # (the executables are named by role and window shape)
    assert stub.warm_order[0].startswith("cko_eval_post_")
    assert all(name.startswith("cko_match_") for name in stub.warm_order[1:])
    assert not any("eval_post" in name for name in stub.warm_order[1:])


def test_host_twin_window_never_enters_the_launch_table(monkeypatch):
    """A window with a stage that is not resident takes the host twins
    and leaves the engine's launch table empty; once every stage is
    resident the next window fills it and the one after launches from
    it, with the same verdicts."""
    reqs = [
        HttpRequest(uri="/?q=lazy-table-probe-7"),
        HttpRequest(uri="/?q=benign"),
    ]
    rules = (
        "SecRuleEngine On\n"
        'SecRule ARGS "@rx lazy-table-probe-[0-9]+" '
        '"id:901,phase:2,deny,status:403"\n'
    )
    monkeypatch.setenv("CKO_VALUE_CACHE_MB", "0")  # one window shape
    eager = WafEngine(rules)
    want = [_vt(v) for v in eager.evaluate(reqs)]

    monkeypatch.setenv("CKO_LAZY_TIERS", "1")
    lazy = WafEngine(rules)
    _generation, table = lazy._launch_table
    s0 = EXEC_CACHE.stats()
    with monkeypatch.context() as m:
        # The post stage alone is "still compiling": a mixed window.
        real = TierCompiler.ensure
        m.setattr(
            TierCompiler,
            "ensure",
            lambda self, spec, key=None: spec[0] != "post"
            and real(self, spec, key),
        )
        for _ in range(2):
            assert [_vt(v) for v in lazy.evaluate(reqs)] == want
            assert table == {}
    s1 = EXEC_CACHE.stats()
    assert s1["host_twin_windows"] - s0["host_twin_windows"] == 2
    assert s1["launch_plan_misses"] - s0["launch_plan_misses"] == 2
    assert s1["launch_plan_hits"] == s0["launch_plan_hits"]
    assert not lazy.warmed

    assert [_vt(v) for v in lazy.evaluate(reqs)] == want  # resolves, fills
    assert len(table) == 1
    assert [_vt(v) for v in lazy.evaluate(reqs)] == want  # launches from it
    s2 = EXEC_CACHE.stats()
    assert s2["launch_plan_misses"] - s1["launch_plan_misses"] == 1
    assert s2["launch_plan_hits"] - s1["launch_plan_hits"] == 1
    assert s2["device_windows"] - s1["device_windows"] == 2
    assert s2["host_twin_windows"] == s1["host_twin_windows"]
    assert lazy.warmed
