"""Shape-canonical executable reuse (ISSUE 2).

The compiled executable is a function of the SHAPE SIGNATURE only —
tier shapes, mask tuple, model layout — with every DFA/segment table a
runtime operand. These tests pin the three serving-facing invariants:

1. two DISTINCT rulesets sharing one shape signature reuse ONE
   executable yet produce their own correct (host-fallback-parity)
   verdicts;
2. a hot reload on an unchanged signature performs ZERO new compiles;
3. N tenants on M distinct rulesets hold M resident engines.
"""

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from coraza_kubernetes_operator_tpu.engine.compile_cache import (
    EXEC_CACHE,
    batch_signature,
)
from coraza_kubernetes_operator_tpu.engine.request import HttpRequest
from coraza_kubernetes_operator_tpu.engine.waf import WafEngine

# Byte-class-isomorphic patterns (1:1 letter remap): the minimized DFAs
# have identical state/class counts, so the two rulesets' device tables
# have identical shapes — the executable-sharing scenario.
RULES_A = (
    "SecRuleEngine On\n"
    'SecRule ARGS "@rx abcdef(?:gh|ij)+k" "id:100,phase:2,deny,status:403"\n'
)
RULES_B = (
    "SecRuleEngine On\n"
    'SecRule ARGS "@rx mnopqr(?:st|uv)+w" "id:100,phase:2,deny,status:403"\n'
)


def _requests():
    return [
        HttpRequest(uri="/?q=abcdefghijk"),  # matches A only
        HttpRequest(uri="/?q=mnopqrstuvw"),  # matches B only
        HttpRequest(uri="/?q=benign-value"),
    ]


def test_distinct_rulesets_share_one_executable():
    eng_a = WafEngine(RULES_A)
    eng_b = WafEngine(RULES_B)
    reqs = _requests()
    assert eng_a.batch_signature(reqs) == eng_b.batch_signature(reqs)

    verdicts_a = eng_a.evaluate(reqs)
    hits0, misses0, _ = EXEC_CACHE.snapshot()
    verdicts_b = eng_b.evaluate(reqs)
    hits1, misses1, _ = EXEC_CACHE.snapshot()

    # Engine B rode engine A's executables: zero new compiles, only
    # hits (one per split-dispatch stage — tier matchers + post).
    assert misses1 == misses0
    assert hits1 > hits0

    # ... and still produced ITS OWN verdicts (tables are operands).
    assert [v.interrupted for v in verdicts_a] == [True, False, False]
    assert [v.interrupted for v in verdicts_b] == [False, True, False]
    assert verdicts_a[0].rule_id == verdicts_b[1].rule_id == 100


def test_shared_executable_host_fallback_parity():
    """Verdicts off the shared executable match the no-JAX host fallback
    evaluator bit-for-bit, for BOTH rulesets."""
    for rules in (RULES_A, RULES_B):
        eng = WafEngine(rules)
        reqs = _requests()
        device = eng.evaluate(reqs)
        host = eng.host_fallback.evaluate(reqs)
        for d, h in zip(device, host):
            assert (d.interrupted, d.status, d.rule_id, d.matched_ids) == (
                h.interrupted,
                h.status,
                h.rule_id,
                h.matched_ids,
            )


def test_reload_unchanged_signature_zero_compiles():
    """The hot-reload path builds a FRESH engine from the same ruleset
    text; its first batch must not trigger any XLA compile."""
    reqs = _requests()
    eng1 = WafEngine(RULES_A)
    eng1.evaluate(reqs)  # ensures the signature's executable is resident

    _, misses0, compile_s0 = EXEC_CACHE.snapshot()
    eng2 = WafEngine(RULES_A)  # what RuleReloader.poll_once does on a swap
    verdicts = eng2.evaluate(reqs)
    _, misses1, compile_s1 = EXEC_CACHE.snapshot()

    assert misses1 == misses0, "reload on unchanged signature recompiled"
    assert compile_s1 == compile_s0
    assert [v.interrupted for v in verdicts] == [True, False, False]


def test_prewarm_compiles_off_path_then_serves_hit():
    eng = WafEngine(RULES_B)
    canary = [HttpRequest(uri="/__warm__", headers=[("host", "h")])]
    out = eng.prewarm(canary)
    # First prewarm for this signature either compiles or finds it
    # resident from an earlier test run; a SECOND prewarm must not.
    assert out["compiled"] in (True, False)
    _, misses0, _ = EXEC_CACHE.snapshot()
    assert eng.prewarm(canary)["compiled"] is False
    verdicts = eng.evaluate(canary)
    _, misses1, _ = EXEC_CACHE.snapshot()
    assert misses1 == misses0, "evaluate after prewarm should be compile-free"
    assert not verdicts[0].interrupted


def test_batch_signature_canonical_under_host_metadata():
    """block_kinds/block_cost are host-side planning metadata: they must
    not enter the executable key (WafModel flattens them as ())."""
    import jax

    eng = WafEngine(RULES_A)
    leaves, treedef = jax.tree_util.tree_flatten(eng.model)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.block_kinds == ()
    assert rebuilt.block_cost == ()
    # Signature helper is stable and hashable.
    sig = batch_signature((eng.model,), ())
    assert hash(sig) == hash(batch_signature((eng.model,), ()))


def test_tenant_engines_dedupe_by_ruleset_hash():
    """32 tenants over 4 distinct rulesets hold 4 engines (``BASELINE.json``
    config 5's shape) — resident engines key on content hash, not tenant id."""
    from coraza_kubernetes_operator_tpu.sidecar.tenants import (
        SharedEngineFactory,
    )

    built = []

    def factory(rules):
        eng = WafEngine(rules)
        built.append(eng)
        return eng

    shared = SharedEngineFactory(factory)
    texts = [
        "SecRuleEngine On\n"
        f'SecRule ARGS "@contains tenant-model-{i}" '
        f'"id:{200 + i},phase:2,deny,status:403"\n'
        for i in range(4)
    ]
    engines = [shared(texts[i % 4]) for i in range(32)]
    assert len(built) == 4
    assert len({id(e) for e in engines}) == 4
    assert shared.dedup_hits == 28
    assert shared.resident == 4
    # Routing correctness survives sharing: each tenant's engine blocks
    # its own model's payload and passes a sibling's.
    v = engines[5].evaluate_one(HttpRequest(uri="/?q=tenant-model-1"))
    assert v.interrupted and v.rule_id == 201
    assert not engines[5].evaluate_one(
        HttpRequest(uri="/?q=tenant-model-2")
    ).interrupted


def test_tenant_manager_wraps_factory_and_counts_residents():
    from coraza_kubernetes_operator_tpu.cache import (
        RuleSetCache,
        RuleSetCacheServer,
    )
    from coraza_kubernetes_operator_tpu.sidecar.tenants import TenantManager

    cache = RuleSetCache()
    text = (
        "SecRuleEngine On\n"
        'SecRule ARGS "@contains shared-attack" '
        '"id:300,phase:2,deny,status:403"\n'
    )
    keys = [f"ns{i}/rs" for i in range(6)]
    for k in keys:
        cache.put(k, text)  # every tenant polls the SAME ruleset
    srv = RuleSetCacheServer(cache, host="127.0.0.1", port=0)
    srv.start()
    try:
        mgr = TenantManager(
            cache_base_url=f"http://127.0.0.1:{srv.port}",
            tenant_keys=keys,
            poll_interval_s=3600,
        )
        assert mgr.poll_all_once() == 6
        assert mgr.resident_engines() == 1
        assert mgr.engine_dedup_hits == 5
        assert mgr.engine_for("ns0/rs") is mgr.engine_for("ns5/rs")
        v = mgr.engine_for("ns3/rs").evaluate_one(
            HttpRequest(uri="/?q=shared-attack")
        )
        assert v.interrupted and v.rule_id == 300
    finally:
        srv.stop()


def test_exec_cache_thread_safe_single_resident():
    """Concurrent same-signature dispatches keep ONE resident executable
    and produce identical results."""
    eng = WafEngine(RULES_A)
    reqs = _requests()
    # Two warm passes: the first populates the cross-batch VALUE cache,
    # which changes the second pass's tier shapes (cached rows replace
    # matcher rows) — the steady-state signature the threads then race.
    eng.evaluate(reqs)
    eng.evaluate(reqs)
    entries0 = len(EXEC_CACHE)
    results = [None] * 4
    errs = []

    def work(i):
        try:
            results[i] = [v.interrupted for v in eng.evaluate(reqs)]
        except Exception as e:  # pragma: no cover - diagnostic
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert all(r == [True, False, False] for r in results)
    assert len(EXEC_CACHE) == entries0


def test_degraded_probe_prewarms_before_canary():
    """The promotion probe AOT-prewarms the canary signature off the
    serving path before proving the device with a real batch."""
    from coraza_kubernetes_operator_tpu.sidecar.degraded import (
        DegradedModeManager,
    )

    calls = []

    class FakeEngine:
        warmed = False

        def prewarm(self, requests=None):
            calls.append(("prewarm", len(requests or [])))
            return {"compiled": True, "wall_s": 0.01}

        def evaluate(self, requests):
            calls.append(("evaluate", len(requests)))
            self.warmed = True
            return [None] * len(requests)

    mgr = DegradedModeManager(probe_backoff_s=0.01)
    eng = FakeEngine()
    mgr.ensure_probe(eng)
    deadline = threading.Event()
    for _ in range(200):
        if eng.warmed:
            break
        deadline.wait(0.05)
    assert eng.warmed
    assert calls[0][0] == "prewarm"
    assert ("evaluate", 1) in calls
    mgr.stop()


# -- where the persistent cache goes (ISSUE 22) ------------------------------
#
# Each case runs configure_persistent_cache in a fresh interpreter: the
# wiring is process-global (jax.config + a module latch) and conftest has
# already pointed this process at tests/.jax_cache.

REPO = Path(__file__).resolve().parents[1]

_PROBE = """
import json, os, sys
import jax
from coraza_kubernetes_operator_tpu.engine.compile_cache import (
    configure_persistent_cache, resolve_cache_dir,
)
flag, default = json.loads(sys.argv[1])
print(json.dumps({
    "resolved": resolve_cache_dir(flag, default=default),
    "returned": configure_persistent_cache(flag, default=default),
    "jax": jax.config.jax_compilation_cache_dir,
}))
"""


def _probe_cache_dir(cwd, flag=None, default=False, **env_dirs):
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "CKO_COMPILE_CACHE_DIR")
    }
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO), **env_dirs)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, json.dumps([flag, default])],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_jax_cache_env_wins_over_flag_and_repo_knob(tmp_path):
    jax_dir, cko_dir, flag_dir = (tmp_path / n for n in ("jax", "cko", "flag"))
    got = _probe_cache_dir(
        tmp_path,
        flag=str(flag_dir),
        default=True,
        JAX_COMPILATION_CACHE_DIR=str(jax_dir),
        CKO_COMPILE_CACHE_DIR=str(cko_dir),
    )
    assert got == {"resolved": str(jax_dir), "returned": str(jax_dir), "jax": str(jax_dir)}
    assert jax_dir.is_dir() and not cko_dir.exists() and not flag_dir.exists()


def test_repo_knob_and_flag_apply_when_jax_env_unset(tmp_path):
    cko_dir, flag_dir = tmp_path / "cko", tmp_path / "flag"
    got = _probe_cache_dir(tmp_path, CKO_COMPILE_CACHE_DIR=str(cko_dir))
    assert got["jax"] == got["returned"] == str(cko_dir)
    got = _probe_cache_dir(
        tmp_path, flag=str(flag_dir), CKO_COMPILE_CACHE_DIR=str(cko_dir)
    )
    assert got["jax"] == got["returned"] == str(flag_dir)
    got = _probe_cache_dir(tmp_path, flag="0", default=True)
    assert got == {"resolved": None, "returned": None, "jax": None}


def test_default_cache_dir_is_one_fixed_path_in_the_checkout(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir()
    b.mkdir()
    got_a = _probe_cache_dir(a, default=True)
    got_b = _probe_cache_dir(b, default=True)
    assert got_a == got_b
    assert got_a["jax"] == got_a["returned"] == str(REPO / ".jax_bench_cache")
    # Nothing selects a directory unless an entrypoint asks for the default.
    assert _probe_cache_dir(a)["jax"] is None


# -- composed keys and the launch table (ISSUE 29) ---------------------------


def _old_key(jitted, args, statics):
    """The key as it was before keys were composed: one signature over
    the whole argument list, model tables included."""
    name = getattr(jitted, "__name__", None) or str(jitted)
    return (name,) + batch_signature(args, tuple(sorted(statics.items())))


def _window(
    rows=16,
    width=32,
    requests=8,
    mask=None,
    max_phase=2,
    cached_rows=None,
    pipelines=1,
    n_tiers=1,
    fill=0,
):
    """A window's operands as ``tier_tensors`` lays them out, at the
    given shapes: ``(tiers, numvals, masks, cached, max_phase)``."""
    import numpy as np

    def tier(u):
        i32 = lambda *shape: np.full(shape, fill, dtype=np.int32)  # noqa: E731
        return (
            np.full((u, width), fill, dtype=np.uint8),
            i32(u),
            i32(u),
            i32(u),
            i32(u),
            i32(u),
            np.zeros((pipelines, u, width), dtype=np.uint8),
            i32(pipelines, u),
            i32(u),
        )

    tiers = tuple(tier(rows * (i + 1)) for i in range(n_tiers))
    cached = None
    if cached_rows is not None:
        cached = tuple(np.zeros((cached_rows, 4), dtype=np.uint8) for _ in tiers)
    return tiers, np.zeros((requests, 4), dtype=np.int32), (mask,) * n_tiers, cached, max_phase


def _keys(eng, window):
    """Per executable of the window: (composed from the engine's kept
    model signature, composed from the whole spec, the old key); and the
    launch table's signature of the window."""
    from coraza_kubernetes_operator_tpu.engine.tier_compile import spec_key
    from coraza_kubernetes_operator_tpu.engine.waf import shape_signature

    tiers, numvals, masks, cached, max_phase = window
    match_specs, post_spec, _pairs = eng._tier_specs(
        tiers, numvals, max_phase=max_phase, masks=masks, cached=cached
    )
    specs = match_specs + [post_spec]
    composed = tuple(spec_key(s, eng._model_sig) for s in specs)
    whole = tuple(spec_key(s) for s in specs)
    old = tuple(_old_key(s[2], s[3], s[4]) for s in specs)
    table = shape_signature((tiers, numvals, cached), (masks, max_phase))
    return composed, whole, old, table


_BASE = dict(cached_rows=16)
_VARIANTS = {
    "tier_rows": dict(rows=32),
    "tier_width": dict(width=64),
    "request_bucket": dict(requests=16),
    "mask": dict(mask=5),
    "max_phase": dict(max_phase=1),
    "cached_bucket": dict(cached_rows=32),
    "no_cached_rows": dict(cached_rows=None),
    "host_pipelines": dict(pipelines=2),
    "tier_count": dict(n_tiers=2),
    "values_only": dict(fill=7),  # the one pair whose keys must be EQUAL
}



@pytest.mark.parametrize("what", sorted(_VARIANTS))
def test_composed_key_partitions_calls_like_the_old_key(what):
    """Keys composed from the kept model signature equal the keys
    composed from the whole spec, and separate two windows exactly where
    the old whole-pytree key separated them; so does the launch table's
    signature of the window."""
    eng = WafEngine(RULES_A)
    a = _keys(eng, _window(**_BASE))
    b = _keys(eng, _window(**{**_BASE, **_VARIANTS[what]}))
    for composed, whole, _old, _table in (a, b):
        assert composed == whole
        assert hash(composed) == hash(whole)
    old_equal = a[2] == b[2]
    assert old_equal == (what == "values_only")
    assert (a[0] == b[0]) == old_equal
    assert (a[3] == b[3]) == old_equal
    # Executable by executable, where the two windows have as many.
    if len(a[0]) == len(b[0]):
        for ca, cb, oa, ob in zip(a[0], b[0], a[2], b[2]):
            assert (ca == cb) == (oa == ob)


def test_same_layout_rulesets_compose_equal_keys():
    eng_a = WafEngine(RULES_A)
    eng_b = WafEngine(RULES_B)
    assert eng_a._model_sig is not eng_b._model_sig
    assert eng_a._model_sig == eng_b._model_sig
    assert _keys(eng_a, _window())[0] == _keys(eng_b, _window())[0]
    # A ruleset of another layout composes other keys.
    eng_c = WafEngine(RULES_A + 'SecRule ARGS "@rx x+y" "id:101,phase:2,pass"\n')
    assert eng_c._model_sig != eng_a._model_sig
    assert _keys(eng_c, _window())[0] != _keys(eng_a, _window())[0]


def test_warm_windows_never_walk_the_model(monkeypatch):
    """Ten warm windows: no signature is taken of anything that holds
    the model, each launches from the table, nothing compiles."""
    from coraza_kubernetes_operator_tpu.engine import compile_cache, waf
    from coraza_kubernetes_operator_tpu.models.waf_model import WafModel

    eng = WafEngine(RULES_A)
    reqs = _requests()
    want = [v.interrupted for v in eng.evaluate(reqs)]
    eng.evaluate(reqs)  # the value cache's rows: another window shape

    walks = []
    real = compile_cache.batch_signature

    def counting(args, static_kwargs):
        if any(isinstance(a, WafModel) for a in args):
            walks.append(args)
        return real(args, static_kwargs)

    monkeypatch.setattr(compile_cache, "batch_signature", counting)
    monkeypatch.setattr(waf, "shape_signature", counting)
    before = EXEC_CACHE.stats()
    for _ in range(10):
        assert [v.interrupted for v in eng.evaluate(reqs)] == want
    after = EXEC_CACHE.stats()

    assert walks == []
    assert after["launch_plan_hits"] - before["launch_plan_hits"] == 10
    assert after["launch_plan_misses"] == before["launch_plan_misses"]
    assert after["device_windows"] - before["device_windows"] == 10
    for flat in ("misses", "bypasses", "host_twin_windows"):
        assert after[flat] == before[flat]
    # One hit per executable called, as before the table.
    assert after["hits"] - before["hits"] == 10 * 2
    # The counting wrapper does see a walk where one is made.
    compile_cache.model_signature(eng.model)
    assert len(walks) == 1


def test_table_hit_keeps_the_aot_rejection_fallback(monkeypatch):
    """An executable that rejects its arguments when called from the
    launch table is bypassed to the plain jit dispatch and counted, as
    on the spec-by-spec path: the window's verdicts do not move and the
    rejected call is no hit."""
    monkeypatch.setenv("CKO_VALUE_CACHE_MB", "0")  # one window shape
    eng = WafEngine(RULES_A)
    reqs = _requests()
    want = [(v.interrupted, v.rule_id) for v in eng.evaluate(reqs)]

    def rejecting(*_args, **_kwargs):
        raise TypeError("compiled for another signature")

    _generation, table = eng._launch_table
    (sig, (match_stages, post_stage, long_scans)), = table.items()
    assert long_scans == (False,) * len(match_stages)
    table[sig] = ([(k, fn, rejecting, st) for k, fn, _c, st in match_stages], post_stage,
                  long_scans)

    before = EXEC_CACHE.stats()
    assert [(v.interrupted, v.rule_id) for v in eng.evaluate(reqs)] == want
    after = EXEC_CACHE.stats()
    assert after["bypasses"] - before["bypasses"] == len(match_stages)
    assert after["hits"] - before["hits"] == 1  # the post stage alone
    assert after["launch_plan_hits"] - before["launch_plan_hits"] == 1
    assert after["device_windows"] - before["device_windows"] == 1
