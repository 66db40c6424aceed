"""Per-tier parallel compile manager (cold-compile collapse).

The split dispatch (``models/waf_model.match_tier_packed`` +
``eval_post_tiered``) turns one monolithic executable into a handful of
independent ones — one matcher per tier shape plus one post stage. This
manager owns HOW those executables get compiled:

- **Parallel**: compiles dispatch across a small thread pool. XLA
  releases the GIL for the whole backend compile, so N tier compiles
  genuinely overlap on N cores instead of serializing behind one
  monolithic trace (``CKO_COMPILE_WORKERS``, default 4). XLA compiles
  on this host's cores, so on a host with fewer than four the workers
  share them; the number is a default, not a measurement.
- **Smallest-first**: pending compiles are submitted in ascending cost
  order (post stage first — it is the cheapest and EVERY verdict needs
  it), so the first tier able to serve from device is the smallest one,
  not the largest. First-verdict latency after a cold start is gated on
  the smallest group's compile; ``submitted`` records the order so tests
  can pin that.
- **Lazy-capable**: ``ensure`` is the non-blocking probe the lazy
  dispatch mode uses — resident executables dispatch immediately, the
  rest are enqueued and the caller routes the tier through the host
  fallback until the executable lands (the degraded-mode promotion
  pattern, applied per tier instead of per engine).

All compiles flow through ``EXEC_CACHE.warm`` so residency, hit/miss
accounting, and the persistent disk cache behave the same for every
stage. Per-label compile seconds feed ``cko_compile_tier_s``.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ..utils import get_logger
from .compile_cache import EXEC_CACHE, model_signature, stage_key

log = get_logger("engine.tier_compile")

# A compile spec is (label, cost, jitted, args, static_kwargs):
# label is the stable human name ("post", "match:1024x64"), cost the
# smallest-first sort key (~rows x width; 0 for the post stage).


def spec_key(spec, model_sig: tuple | None = None) -> tuple:
    """The EXEC_CACHE key a spec's dispatch will use. This walks the spec's
    first argument, the whole model; a caller that keeps that half
    (``compile_cache.model_signature``; ``WafEngine`` does, beside its
    model) passes it and pays for the window's operands alone."""
    _label, _cost, jitted, args, statics = spec
    if model_sig is None:
        model_sig = model_signature(args[0])
    return stage_key(jitted, model_sig, args[1:], statics)


class TierCompiler:
    """Thread-pooled, smallest-first compilation of tier executables."""

    def __init__(self, workers: int | None = None):
        self._lock = threading.Lock()
        self._pool: ThreadPoolExecutor | None = None
        self._workers = workers
        self._inflight: dict[tuple, object] = {}  # key -> Future
        # label -> cumulative XLA wall seconds spent minting executables
        # with that label (cko_compile_tier_s).
        self.tier_s: dict[str, float] = {}
        # (label, cost) in submission order — smallest-first is the
        # contract (tests/test_lazy_tiers.py pins it).
        self.submitted: list[tuple[str, float]] = []
        # label -> free-form metadata annotated at tier-selection time
        # (engine startup stamps the automata composition here so stats
        # can say WHAT each compiled stage contains — e.g. how
        # many dfa-hot blocks rode into the matcher trace).
        self.meta: dict[str, dict] = {}

    def annotate(self, label: str, **meta) -> None:
        """Attach/merge metadata onto a stage label. Purely descriptive:
        never keys the executable cache, only surfaces through
        ``stats()``-adjacent reporting."""
        with self._lock:
            self.meta.setdefault(label, {}).update(meta)

    def _ensure_pool(self) -> ThreadPoolExecutor:
        if self._pool is None:
            # Backend compiles release the GIL and run on this host's
            # cores, one compile per worker. Four is not tied to
            # cpu_count: fewer cores means the workers share them
            # (tracing holds the GIL anyway), more cores go unused by a
            # single cold start.
            workers = self._workers or int(
                os.environ.get("CKO_COMPILE_WORKERS", "0")
            ) or 4
            self._pool = ThreadPoolExecutor(
                max_workers=max(1, workers),
                thread_name_prefix="cko-tier-compile",
            )
        return self._pool

    def resident(self, spec, key: tuple | None = None) -> bool:
        """Probe without counting a cache hit (the pre-warm peek).
        ``key``, here and below, is ``spec_key(spec)`` where the caller
        already computed it."""
        if key is None:
            key = spec_key(spec)
        return EXEC_CACHE._lookup(key, count_hit=False) is not None

    def _compile_one(self, key: tuple, spec) -> bool:
        label, _cost, jitted, args, statics = spec
        t0 = time.perf_counter()
        try:
            minted = EXEC_CACHE.warm(jitted, args, statics, key=key)
        finally:
            with self._lock:
                self._inflight.pop(key, None)
        if minted:
            dt = time.perf_counter() - t0
            with self._lock:
                self.tier_s[label] = self.tier_s.get(label, 0.0) + dt
        return minted

    def _submit(self, spec, key: tuple) -> object | None:
        """Enqueue one spec (deduped on key). Returns the Future, or
        None when the executable is already resident."""
        if EXEC_CACHE._lookup(key, count_hit=False) is not None:
            return None
        with self._lock:
            fut = self._inflight.get(key)
            if fut is None:
                self.submitted.append((spec[0], float(spec[1])))
                fut = self._ensure_pool().submit(self._compile_one, key, spec)
                self._inflight[key] = fut
        return fut

    def ensure(self, spec, key: tuple | None = None) -> bool:
        """Non-blocking: True when the spec's executable is resident and
        can dispatch now; otherwise enqueue its compile (idempotent) and
        return False so the caller routes through the host fallback."""
        if key is None:
            key = spec_key(spec)
        if self.resident(spec, key):
            return True
        self._submit(spec, key)
        return False

    def compile_all(self, specs, keys=None) -> int:
        """Blocking parallel compile of every non-resident spec,
        submitted smallest-first. Returns how many executables this call
        minted (0 = everything was already resident)."""
        if keys is None:
            keys = [spec_key(s) for s in specs]
        pending = [(s, k) for s, k in zip(specs, keys) if not self.resident(s, k)]
        pending.sort(key=lambda sk: sk[0][1])
        futures = [f for f in (self._submit(s, k) for s, k in pending) if f is not None]
        minted = 0
        for f in futures:
            if f.result():
                minted += 1
        if minted:
            log.info(
                "tier executables compiled",
                minted=minted,
                labels=[s[0] for s, _k in pending],
            )
        return minted

    def pending(self) -> int:
        with self._lock:
            return len(self._inflight)

    def stats(self) -> dict[str, float]:
        """label -> cumulative compile seconds (sorted for stable JSON)."""
        with self._lock:
            return {k: round(v, 3) for k, v in sorted(self.tier_s.items())}


# Process-wide singleton: every engine shares the pool and the per-label
# compile-time ledger, mirroring EXEC_CACHE's process-wide sharing.
TIER_COMPILER = TierCompiler()
