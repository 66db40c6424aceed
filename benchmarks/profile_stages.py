"""Stage-by-stage device timing at full-CRS scale (segment tier aware).

Splits eval_waf into: device transforms, segment-block matching (per
block), DFA bank scans (per bank), and post_match — each jitted alone so
the hot spot is unambiguous. Use BENCH-style env knobs:
PROF_RULES (default 800), PROF_BATCH (default 4096), PROF_ITERS (10).
"""

import os
import statistics
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp


N_CHUNKS = int(os.environ.get("PROF_CHUNKS", "8"))


def timeit(fn, *args, iters=10, **kw):
    """Amortized device timing: ONE dispatch steps the stage N_CHUNKS
    times inside ``lax.map`` (first arg perturbed per step so nothing is
    reused), so the per-dispatch host cost is divided out.
    Returns (seconds per single stage call, single-call output)."""
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
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.models.waf_model import post_match
    from coraza_kubernetes_operator_tpu.ops.dfa import scan_dfa_bank
    from coraza_kubernetes_operator_tpu.ops.segment import match_segment_block
    from coraza_kubernetes_operator_tpu.ops.transforms import apply_device_pipeline

    n_rules = int(os.environ.get("PROF_RULES", "800"))
    batch = int(os.environ.get("PROF_BATCH", "4096"))
    iters = int(os.environ.get("PROF_ITERS", "10"))
    engine = WafEngine(synthetic_crs(n_rules))
    m = engine.model

    requests = synthetic_requests(batch, attack_ratio=0.1, seed=1)
    extractions = [engine.extractor.extract(r) for r in requests]
    tensors = engine._tensorize(extractions)
    data, lengths, kind1, kind2, kind3, req_id, numvals, vdata, vlens = [
        jax.device_put(t) for t in tensors
    ]
    print(
        f"rules={n_rules} batch={batch} targets={data.shape[0]} L={data.shape[1]} "
        f"segs={len(m.segs)} banks={len(m.banks)}"
    )
    for i, s in enumerate(m.segs):
        k = s.kernel
        print(
            f"  seg[{i}] pid={m.seg_pipelines[i]} kernel={k.shape} {k.dtype} "
            f"spec_groups={s.spec.n_groups if hasattr(s.spec, 'n_groups') else '?'}"
        )
    for i, b in enumerate(m.banks):
        print(f"  bank[{i}] pid={m.bank_pipelines[i]} states={b.table.shape}")

    # Device transforms per pipeline actually used.
    pids = sorted(set(m.seg_pipelines) | set(m.bank_pipelines))
    tdata = {}
    for pid in pids:
        slot = m.host_variant_index[pid]
        if slot >= 0:
            tdata[pid] = (vdata[slot], vlens[slot])
            print(f"  pid={pid} host variant slot {slot}")
            continue
        f = jax.jit(partial(apply_device_pipeline, transforms=m.pipelines[pid]))
        t, out = timeit(f, data, lengths, iters=iters)
        tdata[pid] = out
        print(f"  transform pid={pid} {m.pipelines[pid]}: {t*1e3:.2f} ms")

    total_match = 0.0
    hits = []
    for i, (seg, pid) in enumerate(zip(m.segs, m.seg_pipelines)):
        f = jax.jit(lambda td, tl, seg=seg: match_segment_block(seg.kernel, seg.spec, td, tl))
        t, out = timeit(f, *tdata[pid], iters=iters)
        total_match += t
        hits.append(out)
        print(f"  match seg[{i}]: {t*1e3:.2f} ms -> {out.shape}")
    for i, (bank, pid) in enumerate(zip(m.banks, m.bank_pipelines)):
        f = jax.jit(lambda td, tl, bank=bank: scan_dfa_bank(bank, td, tl))
        t, out = timeit(f, *tdata[pid], iters=iters)
        total_match += t
        hits.append(out)
        print(f"  scan bank[{i}]: {t*1e3:.2f} ms -> {out.shape}")

    gh = jnp.concatenate(hits, axis=1)
    f = lambda g, *rest: post_match(m, g, *rest, max_phase=2)
    t, out = timeit(f, gh, kind1, kind2, kind3, req_id, numvals, iters=iters)
    print(f"  post_match: {t*1e3:.2f} ms")
    print(f"match total: {total_match*1e3:.2f} ms")

    from coraza_kubernetes_operator_tpu.models.waf_model import eval_waf

    f = lambda d, *rest: eval_waf.__wrapped__(m, d, *rest, max_phase=2)
    t, out = timeit(
        f, data, lengths, kind1, kind2, kind3, req_id, numvals, vdata, vlens,
        iters=iters,
    )
    print(f"full eval_waf: {t*1e3:.2f} ms")


if __name__ == "__main__":
    main()
