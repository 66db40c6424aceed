"""Per-(tier, matcher) device timing for the tiered serving path.

The serving step is ``eval_waf_tiered``: rows split into length tiers,
each tier runs every matcher stage at its own width, one global
post_match. This profiler times every individual stage of that exact
path — per tier: device transforms, each segment block, each DFA bank —
plus post_match, so the matcher-cost matrix is unambiguous.

Env knobs: PROF_RULES (800), PROF_BATCH (2048), PROF_ITERS (5),
PROF_CHUNKS (8).
"""

import os
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", str(Path(__file__).parent.parent / ".jax_bench_cache")
)

import jax
import jax.numpy as jnp

N_CHUNKS = int(os.environ.get("PROF_CHUNKS", "8"))


def timeit(fn, *args, iters=5, **kw):
    """One dispatch steps the stage N_CHUNKS times inside lax.map (first
    arg perturbed per step) — amortizes the per-dispatch host cost."""
    single = fn(*args, **kw)
    jax.block_until_ready(single)

    @jax.jit
    def many(*a):
        def chunk(i):
            first = a[0]
            first = first.at[(0,) * first.ndim].set(i.astype(first.dtype))
            out = fn(first, *a[1:], **kw)
            leaves = jax.tree_util.tree_leaves(out)
            return sum(l.astype(jnp.float32).sum() for l in leaves)

        return jax.lax.map(chunk, jnp.arange(N_CHUNKS, dtype=jnp.int32))

    out = many(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = many(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) / N_CHUNKS, single


def main():
    from coraza_kubernetes_operator_tpu.corpus import synthetic_crs, synthetic_requests
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine, tier_tensors
    from coraza_kubernetes_operator_tpu.models.waf_model import post_match
    from coraza_kubernetes_operator_tpu.ops.dfa import (
        _pallas_vmem_bytes,
        _PALLAS_VMEM_BUDGET,
        scan_dfa_bank,
    )
    from coraza_kubernetes_operator_tpu.ops.segment import (
        conv_n2_cols,
        match_segment_block,
    )
    from coraza_kubernetes_operator_tpu.ops.transforms import apply_device_pipeline

    n_rules = int(os.environ.get("PROF_RULES", "800"))
    batch = int(os.environ.get("PROF_BATCH", "2048"))
    iters = int(os.environ.get("PROF_ITERS", "5"))
    engine = WafEngine(synthetic_crs(n_rules))
    m = engine.model

    requests = synthetic_requests(batch, attack_ratio=0.1, seed=1)
    if engine._native.available:
        tensors = engine._native.tensorize(requests)
    else:
        tensors = engine._tensorize([engine.extractor.extract(r) for r in requests])
    tiers, numvals, masks = engine.tier(tensors)
    print(
        f"rules={n_rules} batch={batch} tiers={len(tiers)} "
        f"segs={len(m.segs)} banks={len(m.banks)} long_banks={len(m.long_banks)}"
    )
    for i, b in enumerate(m.banks):
        fits = (
            _pallas_vmem_bytes(b.n_states, b.n_groups, b.t256.dtype.itemsize, 64)
            <= _PALLAS_VMEM_BUDGET
        )
        print(
            f"  bank[{i}] pid={m.bank_pipelines[i]} S={b.n_states} G={b.n_groups} "
            f"dtype={b.t256.dtype} pallas@64={fits}"
        )
    for i, s in enumerate(m.segs):
        print(
            f"  seg[{i}] pid={m.seg_pipelines[i]} kernel={s.kernel.shape} "
            f"groups={s.n_groups} n2cols={conv_n2_cols(s.spec)}"
        )

    total = 0.0
    grand = {}
    for ti, (data, lengths, k1, k2, k3, rid, vd, vl, uid) in enumerate(tiers):
        data, lengths, vd, vl = map(jax.device_put, (data, lengths, vd, vl))
        print(f"tier[{ti}] rows={data.shape[0]} L={data.shape[1]}")
        tdata = {}
        for pid in sorted(set(m.seg_pipelines) | set(m.bank_pipelines)):
            slot = m.host_variant_index[pid]
            if slot >= 0:
                tdata[pid] = (vd[slot], vl[slot])
                continue
            from functools import partial

            f = jax.jit(partial(apply_device_pipeline, transforms=m.pipelines[pid]))
            t, out = timeit(f, data, lengths, iters=iters)
            tdata[pid] = out
            total += t
            grand[f"transform:{pid}"] = grand.get(f"transform:{pid}", 0) + t
            print(f"  transform pid={pid}: {t*1e3:.2f} ms")
        n_seg_cols = sum(conv_n2_cols(s.spec) for s in m.segs)
        bitmap = data.shape[0] * (data.shape[1] + 2) * max(1, n_seg_cols)
        from coraza_kubernetes_operator_tpu.models.waf_model import _SEG_BITMAP_ELEMS

        use_long = bool(m.long_banks) and bitmap > _SEG_BITMAP_ELEMS
        if use_long:
            for i, (bank, pid) in enumerate(zip(m.long_banks, m.long_bank_pipelines)):
                f = jax.jit(lambda td, tl, bank=bank: scan_dfa_bank(bank, td, tl))
                t, out = timeit(f, *tdata[pid], iters=iters)
                total += t
                grand[f"longbank[{i}]"] = grand.get(f"longbank[{i}]", 0) + t
                print(f"  long bank[{i}] S={bank.n_states} G={bank.n_groups}: {t*1e3:.2f} ms")
        else:
            for i, (seg, pid) in enumerate(zip(m.segs, m.seg_pipelines)):
                f = jax.jit(
                    lambda td, tl, seg=seg: match_segment_block(seg.kernel, seg.spec, td, tl)
                )
                t, out = timeit(f, *tdata[pid], iters=iters)
                total += t
                grand[f"seg[{i}]"] = grand.get(f"seg[{i}]", 0) + t
                print(f"  seg[{i}]: {t*1e3:.2f} ms")
        for i, (bank, pid) in enumerate(zip(m.banks, m.bank_pipelines)):
            f = jax.jit(lambda td, tl, bank=bank: scan_dfa_bank(bank, td, tl))
            t, out = timeit(f, *tdata[pid], iters=iters)
            total += t
            grand[f"bank[{i}]"] = grand.get(f"bank[{i}]", 0) + t
            print(f"  bank[{i}] S={bank.n_states} G={bank.n_groups}: {t*1e3:.2f} ms")

    # post_match on the concatenated pair rows.
    import numpy as np

    n_groups = m.e_lg.shape[0]
    pair_rows = sum(t[5].shape[0] for t in tiers)
    gh = jnp.asarray(np.zeros((pair_rows, n_groups), dtype=bool))
    k1 = jnp.concatenate([jnp.asarray(t[2]) for t in tiers])
    k2 = jnp.concatenate([jnp.asarray(t[3]) for t in tiers])
    k3 = jnp.concatenate([jnp.asarray(t[4]) for t in tiers])
    rid = jnp.concatenate([jnp.asarray(t[5]) for t in tiers])
    f = lambda g, *rest: post_match(m, g, *rest, max_phase=2)
    t, out = timeit(f, gh, k1, k2, k3, rid, jnp.asarray(numvals), iters=iters)
    total += t
    grand["post_match"] = t
    print(f"post_match ({pair_rows} pair rows): {t*1e3:.2f} ms")
    print(f"TOTAL (sum of stages): {total*1e3:.2f} ms")
    for k in sorted(grand, key=grand.get, reverse=True)[:12]:
        print(f"  {k}: {grand[k]*1e3:.2f} ms ({100*grand[k]/total:.0f}%)")


if __name__ == "__main__":
    main()
