"""WafEngine: compile once, evaluate request batches on device.

The facade ties together the Seclang compiler, the target extractor, the
device model and shape-bucketing. Shapes are padded to power-of-two buckets
(targets, requests, byte length) so XLA retraces only on bucket growth —
steady-state serving reuses cached executables (the XLA analog of the
reference data plane's compiled-once WASM rules).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import numpy as np

from ..compiler.ruleset import CompiledRuleSet, compile_rules
from ..compiler.transforms_host import apply_pipeline
from ..models.waf_model import WafModel, build_model, tier_seg_plan
from ..observability.stages import HOST_STAGES
from ..observability.stages import current as current_stages
from ..utils import get_logger
from .compile_cache import EXEC_CACHE, model_signature
from .compile_cache import batch_signature as shape_signature
from .request import Extraction, HttpRequest, TargetExtractor

log = get_logger("engine.waf")

_MIN_LEN = 32


def _bucket(n: int, lo: int = 1) -> int:
    size = lo
    while size < n:
        size *= 2
    return size


def _bucket_rows(n: int) -> int:
    """Row-count bucket: power of two up to 2048, then two sizes per
    octave ({3·2^(k-1), 2^k}: 3072, 4096, 6144, 8192, …).

    QUANTIZED lattice (shape quantization): the old 1024-granularity
    above 2048 capped padding waste at ~12% but minted a fresh shape
    signature — and a fresh cold executable — every 1024 rows, which is
    exactly the signature explosion that made a full-CRS cold start
    outlast any budget.
    Two sizes per octave bounds padding waste at ~33% while the distinct
    shape count grows logarithmically, so similar-size batches collapse
    onto the same executables (EXEC_CACHE hits instead of compiles).
    wafbench's ``crs-ingress.wide-u512-c1`` (PR 45) is the one cell that
    runs a row count past 32: 456 unique rows a window, bucket 512."""
    if n <= 2048:
        return _bucket(n)
    size = 2048
    while True:
        if n <= size * 3 // 2:
            return size * 3 // 2
        size *= 2
        if n <= size:
            return size
# Row-level length-tier bounds (buffer widths). A row lands in the
# smallest tier its bytes (and host-variant bytes) fit; tiers with fewer
# than _MIN_TIER_ROWS rows are merged into the next wider tier so a few
# stragglers don't buy extra trace shapes. COARSE lattice (shape
# quantization): ~one bound per two octaves — each bound is a separate
# matcher executable to compile cold, and the matcher cost is linear in
# width, so halving the bound count halves the cold executables at a
# bounded (≤4x-width worst case, same as the old lattice's widest gaps)
# per-row padding cost. The minimum is held to a tier's PAIR rows, before
# dedup and the value cache: ``crs-ingress.wide-u512-c1`` (PR 45, 112
# requests a window) is the one cell whose 64-byte tier passes it, 1,900
# pair rows that the value cache answers to the last (that tier's launch
# is then one padding row, ``1x64``); every other cell's window merges
# into one tier.
_TIER_BOUNDS = (64, 256, 1024, 4096, 16384)
_MIN_TIER_ROWS = 256

# ``WafEngine.body_summary``: bodied requests by the processor that read
# them, their bytes, and the bodies a processor could not parse. The
# native tensorizer counts in this order (cko_result_bodies).
BODY_COUNTERS = (
    "json_total", "urlencoded_total", "multipart_total", "other_total",
    "bytes_total", "parse_errors",
)
_BODY_SLOT = {"JSON": 0, "URLENCODED": 1, "MULTIPART": 2}

# Kind-partitioned matching: rows within a length tier are further split
# into at most CKO_TIER_PARTS partitions by which matcher blocks their
# kinds can reach (models/waf_model.py block_kinds), so header-only rows
# never scan arg-only banks. Partitions below _MIN_PART_ROWS merge into
# the largest one (scanning more blocks is always sound).
import os as _os

_TIER_PARTS = int(_os.environ.get("CKO_TIER_PARTS", "3"))
# Partitions below this row count merge into the largest partition: every
# extra partition is another full matcher trace (compile time) and
# another set of per-stage fixed costs (the flat fused scans made stages
# cheaper but not free — round-5 profiling, which PERF.md calls no
# evidence: 11 partitions cost more in stage overhead than their
# block-skipping saved). No cell reaches it: ``crs-ingress.wide-u512-c1``
# (PR 45), the widest, is 456 unique rows a window.
_MIN_PART_ROWS = int(_os.environ.get("CKO_MIN_PART_ROWS", "1024"))


def _mask_cost(mask: int, block_cost) -> float:
    c = 0.0
    for i, bc in enumerate(block_cost):
        if i >= 62:
            break
        if (mask >> i) & 1:
            c += bc
    return c


def _cluster_masks(values_counts, block_cost, max_parts: int):
    """Greedy cost clustering of block masks into <= max_parts clusters.
    ``values_counts`` is an iterable of (mask, weight); returns a list of
    (member_mask_values, union_mask). Used ONCE per engine (kind-class
    computation) so the set of masks jit ever sees is small and stable —
    per-batch clustering would mint a fresh static mask tuple (and a
    fresh executable) for every traffic mix."""
    clusters = [([int(v)], int(v), int(c)) for v, c in values_counts]
    if len(clusters) > 16:  # cap the O(n^3) greedy; rare kind combos merge first
        clusters.sort(key=lambda cl: -cl[2])
        head, tail = clusters[:15], clusters[15:]
        members, um, rows = [], 0, 0
        for mem, m, r in tail:
            members += mem
            um |= m
            rows += r
        head.append((members, um, rows))
        clusters = head
    while len(clusters) > max_parts:
        best, bi, bj = None, 0, 1
        for i in range(len(clusters)):
            for j in range(i + 1, len(clusters)):
                _mi, ui, ri = clusters[i]
                _mj, uj, rj = clusters[j]
                delta = (
                    (ri + rj) * _mask_cost(ui | uj, block_cost)
                    - ri * _mask_cost(ui, block_cost)
                    - rj * _mask_cost(uj, block_cost)
                )
                if best is None or delta < best:
                    best, bi, bj = delta, i, j
        mi, ui, ri = clusters[bi]
        mj, uj, rj = clusters.pop(bj)
        clusters[bi] = (mi + mj, ui | uj, ri + rj)
    return [(mem, um) for mem, um, _rows in clusters]


def tier_tensors(tensors, kind_lut=None, cache=None):
    """Split one wide tensorized batch into row-level (length x kind
    partition) tiers.

    The matcher's per-row cost is linear in the tier's buffer width
    (conv positions Q = L + 2), and rows are independent until
    post_match, so a long request's short rows (headers, args) should
    never pay the body's width. With ``kind_lut`` (``WafEngine``'s
    kind -> block bitmask table) rows are additionally partitioned by
    which matcher blocks their kinds can reach; each partition's static
    mask lets ``match_tier_packed`` skip unreachable matchers entirely.

    Input is the 9-tuple from ``WafEngine._tensorize`` (or the native
    tensorizer — both produce identical row layouts); output is
    ``(tiers, numvals, masks)`` where tiers is a tuple of per-tier
    9-tuples ``(data, lengths, kind1, kind2, kind3, req_id, vdata,
    vlengths, uid)``: ``data``, ``lengths``, ``vdata`` and ``vlengths``
    go to the tier's ``match_tier_packed`` in the tier's match slab and
    the kinds, req_id and uid to the window's ``eval_post_tiered`` in
    its post slab (``native/arena.py:stage_window`` lays them out by
    the layout the native path's arena stages in). masks is the
    aligned static block-bitmask tuple (entries None when kind_lut is
    absent).

    With ``cache`` (a ``ValueHitCache``), unique rows whose key was
    matched in an earlier batch skip the matcher: the return grows to
    ``(tiers, numvals, masks, cached, miss_keys)`` where cached[i] is
    the tier's bit-packed cached hit rows (or None) and miss_keys[i]
    the keys of the tier's matcher rows (for population after the
    batch). Tier uid indexes the concatenation [matcher rows (bucketed)
    | cached rows]."""
    data, lengths, k1, k2, k3, req_id, numvals, vdata, vlengths = tensors
    n_req = numvals.shape[0]
    h = vdata.shape[0]
    real = np.flatnonzero(req_id < n_req)
    if real.size == 0:
        real = np.array([0], dtype=np.int64)  # keep one padding row
    row_max = lengths.astype(np.int64)
    if h and vlengths.size:
        row_max = np.maximum(row_max, vlengths.max(axis=0))
    cap = data.shape[1]
    bounds = [b for b in _TIER_BOUNDS if b < cap] + [cap]

    raw: list[tuple[int, np.ndarray]] = []
    remaining = real
    for b in bounds:
        fit = row_max[remaining] <= b
        sel = remaining[fit]
        remaining = remaining[~fit]
        if sel.size:
            raw.append((b, sel))
    tiers = []
    masks: list[int | None] = []
    cached: list[np.ndarray | None] = []
    miss_keys: list[list[bytes]] = []

    def emit(sel: np.ndarray, length: int, mask: int | None):
        # VALUE DEDUP: the matcher's output depends only on (bytes,
        # length, variant bytes) — and real traffic repeats values
        # constantly (Host/User-Agent/Accept, header names, hot paths),
        # so a serving batch's rows collapse ~5-15x. Matchers run on the
        # unique rows; post_match keeps one row per original (target,
        # kinds) pair via an index expansion of the group-hit rows.
        parts = [np.ascontiguousarray(data[sel, :length])]
        parts.append(lengths[sel, None].astype(np.int32).view(np.uint8))
        for hi in range(h):
            parts.append(np.ascontiguousarray(vdata[hi][sel, :length]))
            parts.append(vlengths[hi][sel, None].astype(np.int32).view(np.uint8))
        keys = np.concatenate(parts, axis=1)
        _, first_idx, inverse = np.unique(
            keys.view([("", np.void, keys.shape[1])]).ravel(),
            return_index=True,
            return_inverse=True,
        )

        if cache is None:
            usel = sel[first_idx]  # representative row per unique value
            remap = None
            cpk = None
            mkeys: list[bytes] = []
        else:
            # CROSS-BATCH VALUE CACHE: unique rows seen in an earlier
            # batch skip the matcher. Key = partition mask (it decides
            # which hit columns are live) + the dedup key bytes.
            prefix = int(-1 if mask is None else mask).to_bytes(
                8, "little", signed=True
            )
            ukeys = [prefix + keys[i].tobytes() for i in first_idx]
            found, miss = cache.lookup(ukeys)
            usel = sel[first_idx[miss]] if miss else sel[:0]
            mkeys = [ukeys[j] for j in miss]
            u_pad = _bucket_rows(max(1, usel.size))
            cpk = np.zeros(
                (_bucket_rows(max(1, len(found))), cache.packed_len),
                dtype=np.uint8,
            )
            remap = np.zeros(len(ukeys), dtype=np.int32)
            for r, j in enumerate(miss):
                remap[j] = r
            for r, (j, row) in enumerate(sorted(found.items())):
                cpk[r] = row
                remap[j] = u_pad + r

        u = _bucket_rows(max(1, usel.size))
        d = np.zeros((u, length), dtype=np.uint8)
        d[: usel.size] = data[usel, :length]
        lg = np.zeros(u, dtype=np.int32)
        lg[: usel.size] = lengths[usel]
        vd = np.zeros((max(h, 1), u, length), dtype=np.uint8)
        vl = np.zeros((max(h, 1), u), dtype=np.int32)
        if h and usel.size:
            vd[:, : usel.size] = vdata[:, usel, :length]
            vl[:, : usel.size] = vlengths[:, usel]

        p = _bucket_rows(max(1, sel.size))
        kk = []
        for src in (k1, k2, k3):
            a = np.zeros(p, dtype=np.int32)
            a[: sel.size] = src[sel]
            kk.append(a)
        rid = np.full(p, n_req, dtype=np.int32)
        rid[: sel.size] = req_id[sel]
        uid = np.zeros(p, dtype=np.int32)  # pad pairs read unique row 0
        uid[: sel.size] = inverse if remap is None else remap[inverse]
        tiers.append((d, lg, kk[0], kk[1], kk[2], rid, vd, vl, uid))
        masks.append(mask)
        cached.append(cpk)
        miss_keys.append(mkeys)

    # Forward merge: absorb sub-minimum tiers into the next wider bound.
    merged: list[tuple[int, np.ndarray]] = []
    i = 0
    while i < len(raw):
        b, sel = raw[i]
        while sel.size < _MIN_TIER_ROWS and i + 1 < len(raw):
            i += 1
            b = raw[i][0]
            sel = np.concatenate([sel, raw[i][1]])
        merged.append((b, sel))
        i += 1
    # Post-quantization re-check: the forward merge only runs while a
    # NEXT tier exists, so the trailing tier can still land under the
    # minimum (the coarsened bound lattice makes this common — fewer
    # bounds means the tail bucket often holds just a few long rows).
    # Merge it backward into the previous tier (at the wider width) so a
    # handful of stragglers never mint a tiny odd-shaped executable.
    if len(merged) > 1 and merged[-1][1].size < _MIN_TIER_ROWS:
        b_last, sel_last = merged.pop()
        b_prev, sel_prev = merged[-1]
        merged[-1] = (max(b_prev, b_last), np.concatenate([sel_prev, sel_last]))

    for b, sel in merged:
        length = _bucket(max(_MIN_LEN, b))

        if kind_lut is None or _TIER_PARTS <= 1:
            emit(sel, length, None)
            continue
        # kind_lut maps kinds to CLASS masks (a small fixed per-engine
        # set), so pmask takes at most ~2^parts distinct values and the
        # static masks jit sees are bounded and batch-independent.
        pmask = kind_lut[k1[sel]] | kind_lut[k2[sel]] | kind_lut[k3[sel]]
        values = np.unique(pmask)
        parts = [(sel[pmask == v], int(v)) for v in values.tolist()]
        parts = [(s, um) for s, um in parts if s.size]
        # merge sub-minimum partitions into the largest (union mask)
        parts.sort(key=lambda su: -su[0].size)
        while len(parts) > 1 and parts[-1][0].size < _MIN_PART_ROWS:
            s_small, m_small = parts.pop()
            s_big, m_big = parts[0]
            parts[0] = (np.concatenate([s_big, s_small]), m_big | m_small)
        if len(parts) == 1:
            # Single partition: use the scan-everything trace. A content-
            # dependent union mask here would buy nothing (every block is
            # scanned for the one partition anyway at small batches) and
            # each distinct mask value is a fresh jit trace — the
            # latency path must not churn executables per request mix.
            emit(sel, length, None)
        else:
            for s, um in parts:
                emit(s, length, int(um))
    if cache is None:
        return tuple(tiers), numvals, tuple(masks)
    return tuple(tiers), numvals, tuple(masks), tuple(cached), miss_keys


def warmup_request() -> HttpRequest:
    """THE canonical warmup/canary request. The degraded-mode promotion
    probe, ``WafEngine.prewarm``'s default batch, and the rollout
    subsystem's candidate canary + idle self-check all build it here so
    they share ONE shape signature: the executable a probe pre-warms is
    exactly the executable the next canary dispatch (and a staged
    candidate's first shadow check) hits in the cache."""
    return HttpRequest(
        method="GET",
        uri="/__cko_warmup__",
        headers=[("host", "cko-warmup.local"), ("user-agent", "cko-promote/1")],
        body=b"",
    )


@dataclass
class Verdict:
    """Per-request evaluation outcome (the sidecar turns this into 403/200,
    honoring the Engine's failurePolicy — reference
    ``api/v1alpha1/engine_types.go:153-166``)."""

    interrupted: bool
    status: int
    rule_id: int | None
    matched_ids: list[int] = field(default_factory=list)
    scores: dict[str, int] = field(default_factory=dict)

    @property
    def allowed(self) -> bool:
        return not self.interrupted


@dataclass
class InFlightBatch:
    """A dispatched-but-not-collected batch window (pipelined serving).

    ``WafEngine.prepare`` returns one: the batch is tensorized, tiered,
    and its device step ENQUEUED (JAX async dispatch — no host sync has
    happened), so the caller can assemble and dispatch the next window
    while this one's executable runs on device. ``WafEngine.collect``
    blocks on the readback and decodes the verdicts. Decode state (rule
    ids, public counters, value cache) lives on the engine, so
    ``collect`` must be called on the engine whose ``prepare`` built the
    batch — the sidecar batcher pins that pairing per window group
    (``batcher._Group.engine``), which is what lets a hot reload
    mid-flight complete on the engine that dispatched it (verdicts are
    never dropped or re-evaluated)."""

    out: object  # device output: packed array, (packed, tier_hits), or None
    n_live: int
    n_requests: int
    rejected: dict[int, Verdict]
    miss_keys: list | None
    cache_pop: bool  # out carries tier hit rows for value-cache population
    # True when EVERY stage of this window ran on device. Lazy tier
    # compilation (CKO_LAZY_TIERS=1) routes not-yet-compiled tiers
    # through the host fallback — such mixed windows must not flip the
    # engine's ``warmed`` flag (the promotion/timeout machinery reads it
    # as "device executables resident and proven").
    device: bool = True
    # Index -> Verdict replacements applied AFTER decode (blob windows:
    # over-limit rows stay in the tensorized batch — post_match reduces
    # by req_id, so extra rows cannot touch other requests — and their
    # device verdicts are displaced here by the 413/phase-1 outcome,
    # matching prepare()'s row-exclusion semantics bit for bit).
    overrides: dict[int, Verdict] | None = None
    # The window's stage record (observability/stages.py): prepare
    # stamps assemble … post_enqueue on it, collect readback_wait and
    # decode. The four sums below are read off those stamps for this
    # batch alone: host_s = assemble … post_enqueue (it holds the
    # prefilter's device wait), device_s = readback_wait, decode_s =
    # decode, assemble_s = assemble (blob -> dispatch-ready tensors: what
    # ingest_smoke compares across native paths).
    stages: object = None
    host_s: float = 0.0
    device_s: float = 0.0
    decode_s: float = 0.0
    assemble_s: float = 0.0
    # The slabs backing this window's operands (native/arena.py: the
    # tiered native path's arena lease, or the set ``stage_window`` laid
    # a Python-tensorized window out in). collect() releases it after
    # device_get — the device has consumed the host slabs by then, so
    # the arena may recycle them into the next window.
    arena_lease: object = None
    # An injected readback hang (testing/faults.py) is in progress.
    hung: bool = False


class WafEngine:
    """A compiled ruleset plus its jitted batch evaluator."""

    def __init__(self, rules: str | CompiledRuleSet):
        self.compiled = rules if isinstance(rules, CompiledRuleSet) else compile_rules(rules)
        # Two-level automata plan (compiler/automata_plan.py): classifies
        # every group into segment / dfa-hot / prefiltered / nfa under
        # the CKO_AUTOMATA* knobs. build_model gives the hot groups
        # blocks of their own and replaces prefiltered groups' device
        # tables with their over-approximating automata (both scanned in
        # the flat-slot bins with the nfa banks, ops/dfa_flat.py); this
        # engine's dispatch then confirms prefilter positives against the
        # exact DFAs (_confirm_prefilter) so verdicts never change.
        # Direct build_model(crs) callers (tests, the sharded mesh) get
        # the plan-free exact layout.
        from ..compiler.automata_plan import plan_automata

        self.automata_plan = plan_automata(self.compiled)
        self._set_model(build_model(self.compiled, automata=self.automata_plan))
        self.extractor = TargetExtractor(self.compiled)
        self._n_real_rules = len(self.compiled.rules)  # model pads to ≥1 row
        self._rule_ids = np.asarray(
            [r.rule_id for r in self.compiled.rules] or [0], dtype=np.int64
        )
        # rule_id -> phase (the bulk path's body-limit override must not
        # displace a phase-1 interruption, which precedes body ingest).
        self._rule_phase: dict[int, int] = {
            r.rule_id: r.phase for r in self.compiled.rules
        }
        # Rule metadata for the audit log (id/msg/severity/tags).
        self.rule_meta: dict[int, dict] = {
            r.rule_id: {
                "id": r.rule_id,
                "msg": r.msg,
                "severity": r.severity,
                "tags": list(r.tags),
            }
            for r in self.compiled.rules
        }
        # Internal synthetic counters (ctl gating) stay out of verdicts;
        # resolved once here — _decode_packed runs per collected window.
        self._public_counters: list[tuple[int, str]] = [
            (c, name)
            for c, name in enumerate(self.compiled.counters)
            if not name.startswith("__")
        ]
        self._host_pipelines = self.compiled.host_pipelines()
        # Kinds visible to each host pipeline — rows outside the set skip the
        # (sequential, Python) transform on the hot path.
        self._host_pipeline_kinds: list[set[int]] = []
        for pid, _names in self._host_pipelines:
            kinds: set[int] = set()
            for link in self.compiled.links:
                if link.group >= 0 and self.compiled.group_pipeline[link.group] == pid:
                    kinds.update(link.include_kinds)
            self._host_pipeline_kinds.append(kinds)
        # False until the first device batch completes — i.e. while XLA is
        # still compiling this model's executables. The sidecar widens its
        # request timeout for cold engines (server._timeout_for) so a
        # freshly loaded CRS-scale ruleset never times out mid-compile.
        self.warmed = False
        # Native host runtime (C++ extraction + tensorization); falls back
        # to the Python path when the library is absent or the ruleset uses
        # transforms the native tier does not implement.
        from ..native import NativeTensorizer

        self._native = NativeTensorizer(self.compiled)
        # Recent per-window host-assemble walls (blob -> dispatch-ready
        # tensors, both native paths): the ingest smoke's tiered-vs-legacy
        # p50 gate and the stats native block read this. deque.append is
        # atomic under the GIL, so concurrent lane dispatch needs no lock.
        self.blob_assemble_s: deque[float] = deque(maxlen=4096)
        # Kind -> matcher-block bitmask table (kind-partitioned matching):
        # bit i of entry k = block i (segs then banks, build_model order)
        # has a group some rule can reach through kind k. tier_tensors
        # ORs a row's three kind entries into its partition mask; blocks
        # past bit 61 saturate to always-scanned (match_tier).
        n_kinds = self.compiled.vocab.n_kinds
        raw = np.zeros(n_kinds + 1, dtype=np.int64)
        for bi, ks in enumerate(self.model.block_kinds):
            if bi >= 62:
                break
            for k in ks:
                if 0 <= k <= n_kinds:
                    raw[k] |= np.int64(1 << bi)
        # Collapse per-kind masks into <= CKO_TIER_PARTS kind CLASSES
        # (cost-greedy, once per engine): rows then carry one of a small
        # fixed set of class-union masks, so the static mask tuples jit
        # sees are bounded and independent of batch composition.
        # Weight each distinct mask by how many kinds map to it — the
        # greedy clustering then biases class unions toward masks many
        # kinds (hence likely many rows) carry, instead of treating a
        # rare kind combo the same as a hot one (ADVICE r4).
        mask_kinds: dict[int, int] = {}
        for v in raw.tolist():
            if v:
                mask_kinds[int(v)] = mask_kinds.get(int(v), 0) + 1
        distinct = sorted(mask_kinds)
        lut = np.zeros(n_kinds + 1, dtype=np.int64)
        if distinct:
            clusters = _cluster_masks(
                [(v, mask_kinds[v]) for v in distinct],
                self.model.block_cost,
                _TIER_PARTS,
            )
            to_class = {}
            for mem, um in clusters:
                for v in mem:
                    to_class[v] = um
            for k in range(n_kinds + 1):
                lut[k] = to_class.get(int(raw[k]), 0)
        self._kind_block_lut = lut
        # Cross-batch value-hit cache (engine/value_cache.py): matcher
        # results memoized by (partition mask, value bytes).
        # CKO_VALUE_CACHE_MB sets the byte budget (default 256MB; 0
        # disables).
        from .value_cache import ValueHitCache

        # Per matcher block (segs, then the dense blocks: match_tier's
        # column order) its group count: the hit columns in all, and what
        # _host_tier_hits zeroes for a mask-off block.
        self._block_group_counts = tuple(
            [s.n_groups for s in self.model.segs]
            + [b.groups for b in self.model.dense_blocks]
        )
        g_total = sum(self._block_group_counts)
        cache_mb = int(_os.environ.get("CKO_VALUE_CACHE_MB", "256"))
        self.value_cache = (
            ValueHitCache((max(1, g_total) + 7) // 8, cache_mb * 2**20)
            if cache_mb > 0
            else None
        )
        # Split per-tier dispatch (cold-compile collapse): each tier's
        # matcher and the post stage compile as independent executables
        # (engine/tier_compile.py). CKO_LAZY_TIERS=1 routes tiers whose
        # executable is not yet resident through the host fallback while
        # a thread pool compiles them smallest-first (the sidecar entry
        # defaults it on); the default eager mode blocks the first
        # dispatch until every executable landed — still parallel and
        # smallest-first, but deterministic for tests.
        self._lazy = _os.environ.get("CKO_LAZY_TIERS", "0") == "1"
        # Distinct executable shape signatures this engine has dispatched
        # (cko_exec_signatures / CompileReport.exec_signatures).
        self._exec_signatures: set = set()
        # Cumulative over the windows this engine dispatched: matcher
        # tiers launched, their cells (rows x width) and the bytes in
        # them that are not padding (``tiering_summary``); bodied
        # requests the Python extractor read (``body_summary``).
        self._tiering = {
            "windows": 0, "tiers": 0, "cells": 0, "real_bytes": 0, "host_operands": 0,
            "long_scan_launches": 0, "rows": 0, "rows_padded": 0,
        }
        self._bodies = np.zeros(len(BODY_COUNTERS), dtype=np.int64)
        # Host-tier-path helper: _dev_col_of[orig_gid] = device hit
        # column (inverse of model.group_order).
        order = self.model.group_order
        col_of = np.zeros(max(1, len(order)), dtype=np.int64)
        for col, gid in enumerate(order):
            col_of[gid] = col
        self._dev_col_of = col_of
        # Prefilter confirmation counters (metrics/stats): hits = device
        # prefilter positives seen, confirms = positives the exact DFA
        # upheld, false_positives = positives it cleared. Guarded by a
        # lock — the batcher dispatches windows from multiple lanes.
        self.prefilter_stats = {
            "rows": 0,  # (row x prefiltered-column) opportunities examined
            "hits": 0,
            "confirms": 0,
            "false_positives": 0,
            "native_hits": 0,  # hits the native library confirmed
            "native_errors": 0,  # native calls that failed (window re-walked)
        }
        self._prefilter_lock = threading.Lock()
        # The prefiltered groups' exact DFAs and pipelines, handed to the
        # native library once (native/__init__.py:NativeConfirm); which
        # columns it handles is decided here, from what the library
        # exports and the opcodes it has — no knob.
        from ..native import NativeConfirm

        self._prefilter_dev_cols = np.asarray(
            [c for c, _g in self.model.prefilter_cols], dtype=np.int64
        )
        self._native_confirm = NativeConfirm(
            self.compiled, self.model.prefilter_cols, self.model.host_variant_index
        )
        self._native_confirm_failed = False  # the failure is logged once
        # Stamp the automata composition onto the matcher stage label at
        # tier-selection time: tier stats can then report what
        # the compiled matchers actually contain, not just their shapes.
        from .tier_compile import TIER_COMPILER

        _counts = self.automata_plan.counts()
        TIER_COMPILER.annotate(
            "match",
            segment_groups=_counts["segment"],
            dfa_hot_groups=_counts["dfa-hot"],
            prefiltered_groups=_counts["prefiltered"],
            nfa_groups=_counts["nfa"],
            **self._dense_block_counts(),
        )
        # Host fallback evaluator (degraded-mode serving): built lazily on
        # first use — pure NumPy over the same compiled tables, so it can
        # answer while XLA is still compiling or the device is broken.
        self._host_fallback = None
        self._host_fallback_lock = threading.Lock()
        if self.compiled.report.skipped:
            log.info(
                "compiled with skipped rules",
                skipped=len(self.compiled.report.skipped),
                rules=self.compiled.n_rules,
                groups=self.compiled.n_groups,
            )

    @property
    def native_enabled(self) -> bool:
        return self._native.available

    def reinit_device(self) -> None:
        """Re-put the model's device arrays on a fresh backend after a
        device loss (docs/RECOVERY.md): the compiled IR is host state and
        survives, but every ``jnp`` array inside ``self.model`` lived on
        the dead device. Rebuild them and demote ``warmed`` so the next
        device batch re-proves the path before promotion — executables
        are re-fetched from the process/persistent compile caches, so the
        re-put costs array transfers, not XLA compiles."""
        self._set_model(build_model(self.compiled, automata=self.automata_plan))
        self.warmed = False

    def _set_model(self, model: WafModel) -> None:
        """Assign the model with what is resolved once per model: the
        model's half of every stage key (one walk over all its tables,
        here and never under traffic) and an empty launch table (window
        signature -> the resolved executables of a window of that shape,
        filled by ``_dispatch_tiers``). The table is kept with the
        executable cache's generation it was resolved in:
        ``EXEC_CACHE.clear()`` outdates it."""
        self.model: WafModel = model
        self._model_sig = model_signature(model)
        self._launch_table: tuple[int, dict] = (EXEC_CACHE.generation, {})

    @property
    def host_fallback(self):
        """The no-JAX host evaluator over this engine's compiled ruleset
        (``engine/host_fallback.py``); verdicts are bit-identical to
        ``evaluate``. Built once on first access (cheap: NumPy table
        layout, no XLA)."""
        if self._host_fallback is None:
            with self._host_fallback_lock:
                if self._host_fallback is None:
                    from .host_fallback import HostFallbackEvaluator

                    self._host_fallback = HostFallbackEvaluator(
                        self.compiled, extractor=self.extractor
                    )
        return self._host_fallback

    # -- batching -----------------------------------------------------------

    def _tensorize(self, extractions: list[Extraction]):
        body_cap = max(_MIN_LEN, self.compiled.program.request_body_limit)
        rows: list[tuple[int, bytes, tuple[int, int, int]]] = []
        for i, ex in enumerate(extractions):
            for t in ex.targets:
                kinds = self.extractor.kind_ids(t)
                if not kinds:
                    continue  # no rule looks at this target
                # Three kind slots per row; extra kinds get duplicate rows.
                for off in range(0, len(kinds), 3):
                    chunk = kinds[off : off + 3]
                    chunk += [0] * (3 - len(chunk))
                    rows.append((i, t.value[:body_cap], tuple(chunk)))

        for ex in extractions:
            if ex.body_bytes:
                self._bodies[_BODY_SLOT.get(ex.processor, 3)] += 1
                self._bodies[4] += ex.body_bytes
                self._bodies[5] += ex.body_error

        n_req = _bucket(max(1, len(extractions)))
        n_targets = _bucket_rows(max(1, len(rows)))
        h = len(self._host_pipelines)

        # Host-pipeline variants computed per row; length bucket covers all.
        # Only rows whose kinds some rule under that pipeline can see are
        # transformed — the rest stay empty (no rule reads them).
        variants: list[list[bytes]] = [
            [
                apply_pipeline(value, list(names))[:body_cap]
                if any(k in self._host_pipeline_kinds[hi] for k in kinds if k)
                else b""
                for hi, (_, names) in enumerate(self._host_pipelines)
            ]
            for _, value, kinds in rows
        ]
        max_len = max(
            [len(v) for _, v, _ in rows]
            + [len(x) for vs in variants for x in vs]
            + [1]
        )
        length = _bucket(max(_MIN_LEN, max_len))

        data = np.zeros((n_targets, length), dtype=np.uint8)
        lengths = np.zeros(n_targets, dtype=np.int32)
        kind1 = np.zeros(n_targets, dtype=np.int32)
        kind2 = np.zeros(n_targets, dtype=np.int32)
        kind3 = np.zeros(n_targets, dtype=np.int32)
        req_id = np.full(n_targets, n_req, dtype=np.int32)  # padding bucket
        vdata = np.zeros((max(h, 1), n_targets, length), dtype=np.uint8)
        vlengths = np.zeros((max(h, 1), n_targets), dtype=np.int32)

        for row, (ri, value, kinds) in enumerate(rows):
            data[row, : len(value)] = np.frombuffer(value, dtype=np.uint8)
            lengths[row] = len(value)
            kind1[row], kind2[row], kind3[row] = kinds
            req_id[row] = ri
            for hi in range(h):
                hv = variants[row][hi]
                vdata[hi, row, : len(hv)] = np.frombuffer(hv, dtype=np.uint8)
                vlengths[hi, row] = len(hv)

        nv = self.compiled.numvars.n_vars
        numvals = np.zeros((n_req, nv), dtype=np.int32)
        for i, ex in enumerate(extractions):
            for key, value in ex.numerics.items():
                numvals[i, self.compiled.numvars.vars[key]] = value

        # Plain numpy out: jit transfers arguments in one batched dispatch,
        # where per-array jnp.asarray costs one synchronous transfer each.
        return (
            data,
            lengths,
            kind1,
            kind2,
            kind3,
            req_id,
            numvals,
            vdata,
            vlengths,
        )

    # -- public API ---------------------------------------------------------

    def evaluate(self, requests: list[HttpRequest]) -> list[Verdict]:
        """Evaluate a request batch; returns one Verdict per request.

        Row-level length tiering: the batch tensorizes ONCE (native or
        Python path — identical row layout), rows split into per-length
        tiers (``tier_tensors``), each tier's matcher runs at its own
        buffer width, and one global post_match reduces all rows by
        req_id. Tiering is a pure batching policy — row↔tier assignment
        can never change a verdict, only a tier's padding width.

        This is exactly ``collect(prepare(requests))`` — the pipelined
        two-stage path with zero windows in flight — so pipelined and
        synchronous verdicts are bit-identical by construction."""
        if not requests:
            return []
        return self.collect(self.prepare(requests))

    # -- pipelined two-stage serving ----------------------------------------

    def prepare(self, requests: list[HttpRequest]) -> InFlightBatch:
        """Stage 1 of the pipelined hot path: extract + tensorize + tier
        on host, then ENQUEUE the device step (JAX async dispatch) and
        return without any host↔device sync. While the returned window's
        executable runs on device, the caller (``sidecar/batcher.py``)
        assembles and dispatches the next window — host CPU work and
        device compute overlap instead of strictly alternating."""
        rec = current_stages()
        return self._prepare(rec, len(rec.spans), lambda: requests)

    def _prepare(self, rec, since: int, requests_fn) -> InFlightBatch:
        """``prepare`` on the window's stage record; ``requests_fn``
        yields the requests inside ``assemble`` (a blob without the
        native library materializes there)."""
        with rec.stage("assemble"):
            requests = requests_fn()
            batch = self._assemble(requests)
        if isinstance(batch, InFlightBatch):  # nothing left for the device
            return batch
        rejected, n_live, tensors = batch
        inflight = self._enqueue(rec, since, n_live, tensors)
        inflight.n_requests = len(requests)
        inflight.rejected = rejected
        return inflight

    def _enqueue(self, rec, since: int, n_live: int, tensors) -> InFlightBatch:
        """The device half shared by ``prepare`` and ``prepare_blob``:
        enqueue the assembled batch from its staging lease, and read
        its host-stage sums off the record (spans from ``since`` on)."""
        tiers, numvals, masks, cached, mkeys, lease = tensors
        try:
            inflight = self._dispatch_tiers(
                tiers, numvals, n_live, masks=masks, cached=cached,
                miss_keys=mkeys, rec=rec, staged=lease,
            )
        except BaseException:
            if lease is not None:
                lease.release()
            raise
        inflight.assemble_s = rec.total(("assemble",), since)
        inflight.host_s = rec.total(HOST_STAGES, since)
        return inflight

    def _assemble(self, requests: list[HttpRequest]):
        """``prepare``'s host half: the body-limit pre-pass and the
        tensorizer. Returns ``(rejected, n_live, batch tensors)``, or the
        finished InFlightBatch where every request was rejected."""
        prog = self.compiled.program
        rejected: dict[int, Verdict] = {}
        if (
            prog.request_body_access
            and prog.request_body_limit_action == "Reject"
        ):
            # SecRequestBodyLimitAction Reject (Coraza semantics): the
            # body-limit interruption happens at body ingest — AFTER
            # phase 1 already ran on the headers — so a phase-1 deny
            # wins over the 413. ProcessPartial instead evaluates the
            # truncated prefix (the [:limit] slice in extract()). All
            # over-limit requests ride ONE batched phase-1 dispatch (an
            # all-over-limit batch must not serialize per request);
            # this pre-pass is rare and stays synchronous inside prepare.
            over = [
                i
                for i, r in enumerate(requests)
                if len(r.body) > prog.request_body_limit
            ]
            if over:
                exs = [
                    self.extractor.extract(requests[i], phase1_only=True)
                    for i in over
                ]
                early = self._evaluate_extractions(exs, max_phase=1)
                for i, v in zip(over, early):
                    rejected[i] = (
                        v
                        if v.interrupted
                        else Verdict(interrupted=True, status=413, rule_id=None)
                    )
        live = [r for i, r in enumerate(requests) if i not in rejected]
        from ..testing.faults import DeviceFault, poison_marker

        marker = poison_marker()
        if marker is not None and any(marker in r.body for r in live):
            raise DeviceFault(
                "injected poison request (CKO_FAULT_POISON_MARKER)"
            )
        if not live:
            return InFlightBatch(
                out=None,
                n_live=0,
                n_requests=len(requests),
                rejected=rejected,
                miss_keys=None,
                cache_pop=False,
            )
        return rejected, len(live), self._batch_tensors(live)

    def prepare_blob(self, blob: bytes, n_req: int) -> InFlightBatch:
        """``prepare`` for a pre-assembled request blob (the
        ``native.serialize_requests`` wire format): the async ingest
        frontend slices HTTP/1.1 request bytes straight into this
        layout, so a full window tensorizes in one C++ call with zero
        per-request Python object materialization. Without the native
        library the blob materializes into requests and delegates to
        ``prepare`` — same verdicts, just the Python host path.

        SecRequestBodyLimitAction Reject parity with ``prepare``: the
        C++ tensorizer truncates over-limit bodies at the limit, and
        only those few requests materialize for the batched phase-1
        pre-pass; the resulting 413/phase-1 verdicts land as collect
        overrides, displacing the over-limit rows' device verdicts."""
        rec = current_stages()
        since = len(rec.spans)
        if not self._native.available:
            from ..native import blob_requests

            return self._prepare(rec, since, lambda: blob_requests(blob, n_req))
        with rec.stage("assemble"):
            overrides, tensors = self._assemble_blob(blob, n_req)
        inflight = self._enqueue(rec, since, n_req, tensors)
        inflight.overrides = overrides or None
        self._record_assemble(inflight.assemble_s)
        return inflight

    def _assemble_blob(self, blob: bytes, n_req: int):
        """``prepare_blob``'s host half: the body-limit pre-pass and the
        native tensorizer. Returns ``(overrides, batch tensors)``."""
        from ..testing.faults import DeviceFault, poison_marker

        marker = poison_marker()
        if marker is not None and marker in blob:
            raise DeviceFault(
                "injected poison request (CKO_FAULT_POISON_MARKER)"
            )
        prog = self.compiled.program
        overrides: dict[int, Verdict] = {}
        if (
            prog.request_body_access
            and prog.request_body_limit_action == "Reject"
        ):
            from ..native import blob_over_limit, blob_requests

            over = [
                i
                for i in blob_over_limit(blob, prog.request_body_limit)
                if i < n_req
            ]
            if over:
                over_reqs = blob_requests(blob, n_req, wanted=set(over))
                exs = [
                    self.extractor.extract(r, phase1_only=True)
                    for r in over_reqs
                ]
                early = self._evaluate_extractions(exs, max_phase=1)
                for i, v in zip(over, early):
                    overrides[i] = (
                        v
                        if v.interrupted
                        else Verdict(interrupted=True, status=413, rule_id=None)
                    )
        lease = None
        if getattr(self._native, "tiered", False):
            # Tiered window pipeline: blob -> tier-bucketed tensors in
            # arena staging buffers, two GIL-released native calls with
            # only the value-cache probe in Python between them.
            tiers, numvals, masks, cached, mkeys, lease = (
                self._native.tier_blob(
                    blob, n_req, self._kind_block_lut, self.value_cache
                )
            )
        else:
            tensors = self._native.tensorize_blob(blob, n_req)
            tiers, numvals, masks, cached, mkeys = self.tier_cached(tensors)
        return overrides, (tiers, numvals, masks, cached, mkeys, lease)

    def collect(self, inflight: InFlightBatch) -> list[Verdict]:
        """Stage 2 of the pipelined hot path: block on the device
        readback of a ``prepare``d window, populate the value cache from
        its miss rows, and decode the packed verdict array. FIFO
        collection order is the caller's contract (the batcher's
        collector thread drains windows in dispatch order)."""
        try:
            return self._collect(inflight)
        finally:
            # Arena recycle point (tiered native path): device_get on the
            # window's outputs has returned, so execution — and therefore
            # every read of the host staging buffers — is complete. An
            # abandoned window (collect never called) just leaks one
            # buffer set; the arena reallocates on the next miss.
            if inflight.arena_lease is not None:
                inflight.arena_lease.release()

    def device_done(self, inflight: InFlightBatch) -> bool:
        """True once the window's device work has finished: every output
        array is computed (a host twin's NumPy output always is), so all
        that is left of ``collect`` is the host's. Asked by the batcher's
        watchdog from another thread while ``collect`` runs; an injected
        hang stands for a hung device and reads as not done."""
        if inflight.hung:
            return False
        return all(
            leaf.is_ready()
            for leaf in jax.tree_util.tree_leaves(inflight.out)
            if hasattr(leaf, "is_ready")
        )

    def _collect(self, inflight: InFlightBatch) -> list[Verdict]:
        if inflight.out is None:
            return [
                inflight.rejected[i] for i in range(inflight.n_requests)
            ]
        from ..testing.faults import injected_device_hang_s

        hang = injected_device_hang_s()
        if hang > 0:
            inflight.hung = True
            time.sleep(hang)
            inflight.hung = False
        EXEC_CACHE.note_window(
            inflight.out[0] if inflight.cache_pop else inflight.out,
            inflight.device,
        )
        rec = inflight.stages  # the record its prepare stamped on
        since = len(rec.spans)
        with rec.stage("readback_wait"):
            out = jax.device_get(inflight.out)
        with rec.stage("decode"):
            if inflight.cache_pop:
                packed, tier_hits = out
                if self.value_cache is not None and inflight.miss_keys is not None:
                    for keys, hp in zip(inflight.miss_keys, tier_hits):
                        if keys:
                            self.value_cache.insert(keys, hp[: len(keys)])
            else:
                packed = out
            verdicts = self._decode_packed(packed, inflight.n_live)
            if inflight.overrides:
                for i, v in inflight.overrides.items():
                    if 0 <= i < len(verdicts):
                        verdicts[i] = v
        if inflight.device:
            self.warmed = True
        inflight.device_s = rec.total(("readback_wait",), since)
        inflight.decode_s = rec.total(("decode",), since)
        if not inflight.rejected:
            return verdicts
        out: list[Verdict] = []
        it = iter(verdicts)
        for i in range(inflight.n_requests):
            out.append(
                inflight.rejected[i] if i in inflight.rejected else next(it)
            )
        return out

    def _record_assemble(self, dt: float) -> None:
        self.blob_assemble_s.append(dt)

    def native_stats(self) -> dict:
        """Native window-pipeline counters (stats ``native`` block +
        metrics gauges): tiered-path availability, window totals and p50,
        host-assemble p50 across both native paths, and the staging-arena
        pool counters."""
        stats_fn = getattr(self._native, "stats", None)
        out = stats_fn() if stats_fn is not None else {}
        out["available"] = self._native.available
        out["tiered"] = getattr(self._native, "tiered", False)
        recent = sorted(self.blob_assemble_s)
        out["p50_assemble_ms"] = (
            recent[len(recent) // 2] * 1e3 if recent else 0.0
        )
        return out

    def tier(self, tensors):
        """Row-level (length x kind-partition) tiering with this engine's
        kind->class-mask table: returns (tiers, numvals, masks)."""
        return tier_tensors(tensors, self._kind_block_lut)

    def tier_cached(self, tensors):
        """Like ``tier`` but consulting the cross-batch value cache:
        returns (tiers, numvals, masks, cached, miss_keys). Identical to
        ``tier`` + all-miss when the cache is disabled."""
        if self.value_cache is None:
            tiers, numvals, masks = tier_tensors(tensors, self._kind_block_lut)
            return tiers, numvals, masks, None, None
        return tier_tensors(
            tensors, self._kind_block_lut, cache=self.value_cache
        )

    @staticmethod
    def _tier_pairs(tiers) -> tuple:
        """Per tier the ``(kind1, kind2, kind3, req_id, uid)`` pair rows
        the post stage consumes."""
        return tuple((t[2], t[3], t[4], t[5], t[8]) for t in tiers)

    def _tier_specs(
        self, tiers, numvals, max_phase: int = 2, masks=None, cached=None
    ):
        """Build the per-tier compile specs for one batch: one matcher
        spec per tier (``match_tier_packed``) plus one post-stage spec
        (``eval_post_tiered``). Returns ``(match_specs, post_spec,
        pairs)`` where pairs is the per-tier ``(kind1, kind2, kind3,
        req_id, uid)`` tuple the post stage consumes.

        Which operands travel where (``models/slab.py`` is the one
        layout; the specs take their signature from it, as the staging
        arena takes its buffers): a matcher's one window operand is its
        tier's match slab, ``data``, ``vdata``, ``lengths`` and
        ``vlengths`` in one ``uint8`` block; the post stage's are the
        tiers' packed hit rows and the window's post slab, every tier's
        pair rows, ``numvals`` and the ``cached`` blocks in one ``int32``
        block whose ``layout`` is a static argument.

        The window operands are zero-filled PLACEHOLDERS: only
        shapes/dtypes enter the executable-cache key and the lowered
        program, so warming with zeros mints exactly the executable the
        real dispatch calls with live slabs and matcher output."""
        from ..models.slab import match_slab_shape, post_layout, post_slab_words
        from ..models.waf_model import stage_executable

        if masks is None:
            masks = (None,) * len(tiers)
        elif len(masks) != len(tiers):
            # The zips below and in _dispatch_tiers would drop the
            # trailing tiers in silence: missed matches.
            raise ValueError(
                f"masks length {len(masks)} != tiers length {len(tiers)}"
            )
        g = int(self.model.e_lg.shape[0])
        pb = (g + 7) // 8
        match_specs = []
        for t, mask in zip(tiers, masks):
            u, length = t[0].shape
            slab = np.zeros(match_slab_shape(u, length, t[6].shape[0]), dtype=np.uint8)
            match_specs.append(
                (
                    f"match:{u}x{length}",
                    float(u) * float(length),
                    stage_executable("match", f"{u}x{length}"),
                    (self.model, slab),
                    {"mask": mask},
                )
            )
        pairs = self._tier_pairs(tiers)
        ph_hits = tuple(
            np.zeros((t[0].shape[0], pb), dtype=np.uint8) for t in tiers
        )
        layout = post_layout(tiers, numvals, cached)
        post_spec = (
            "post",
            0.0,  # sorts first: every verdict needs the post stage
            stage_executable(
                "eval_post", "_".join(f"{t[0].shape[0]}x{t[0].shape[1]}" for t in tiers)
            ),
            (self.model, ph_hits, np.zeros(post_slab_words(layout), dtype=np.int32)),
            {"max_phase": max_phase, "layout": layout},
        )
        return match_specs, post_spec, pairs

    def _dispatch_tiers(
        self,
        tiers,
        numvals,
        n_requests: int,
        max_phase: int = 2,
        masks=None,
        cached=None,
        miss_keys=None,
        rec=None,
        staged=None,
    ) -> InFlightBatch:
        """Enqueue one tiered batch (no host sync on the device path)
        and return the in-flight handle. The single dispatch site shared
        by the synchronous path (``_verdicts_from_tiers``) and the
        pipelined path (``prepare``) — the two can never drift.

        Split per-tier dispatch (cold-compile collapse): each tier's
        matcher and the post stage are independent executables compiled
        smallest-first across a thread pool (engine/tier_compile.py).
        Eager mode (default) blocks until every executable for this
        batch's shapes is resident, then dispatches — parallel compile,
        deterministic behavior. Lazy mode (CKO_LAZY_TIERS=1) dispatches
        resident stages on device and routes the rest through the host
        fallback twins (``_host_tier_hits`` / ``_host_post``) while
        their compiles land — per-tier degraded-mode promotion. Both
        paths are bit-identical: packbits over the group-hit columns is
        lossless and the host twins are differential-tested against the
        device stages.

        What travels, and when (a host array handed to a launch is one
        transfer; their count is a window's fixed cost): ``staged`` is
        the window laid out in slabs (``native/arena.py``: the native
        path's lease; a window of the Python tensorizer is staged here,
        by copy), and ``tiers``, ``numvals``, ``cached`` are its views.
        Inside ``tier_enqueue`` each matcher is launched on its tier's
        match slab, one operand. ``post_enqueue`` hands the post
        executable the matchers' hit rows (device arrays, but for the
        tiers whose rows the confirm repacked or a host twin computed)
        and the post slab as its one host operand. The slab is NOT put
        on the device early, though nothing in it waits for a matcher:
        a ``jax.device_put`` of its own costs more host time than an
        operand of a launch that happens anyway (0.27 against 0.17 ms
        alone, and the gap grows beside a second lane; PERF.md §6,
        PR 36), and in the host-bound cells nothing hides it.
        ``tiering.host_operands`` counts them all: tiers + 1 a window,
        and one more a repacked tier."""
        from ..testing.faults import on_device_dispatch

        if rec is None:
            rec = current_stages()
        with rec.stage("tier_enqueue"):
            # Fault-injection hook (no-op when the CKO_FAULT_* knobs are
            # unset): stalls cold engines like a real first XLA compile
            # and raises DeviceFault per the configured error rate — the
            # levers tests/test_degraded_mode.py uses to prove the
            # fallback + breaker invariants.
            on_device_dispatch(warmed=self.warmed)
            if masks is None:
                masks = (None,) * len(tiers)
            if staged is None:
                from ..native.arena import stage_window

                staged = stage_window(tiers, numvals, cached)
                tiers, numvals, cached = staged.tiers, staged.numvals, staged.cached
            counts = self._tiering
            counts["windows"] += 1
            rows = self._tier_rows(tiers, numvals, miss_keys)
            for tier, n in zip(tiers, rows):
                counts["tiers"] += 1
                counts["cells"] += tier[0].shape[0] * tier[0].shape[1]
                counts["real_bytes"] += int(np.sum(tier[1]))
                counts["rows"] += n
                counts["rows_padded"] += tier[0].shape[0]
            match_stages, post_stage, long_scans = self._resolve_launch(
                tiers, numvals, max_phase, masks, cached
            )
            model = self.model
            device = True
            tier_hits = []
            from_device = []
            for stage, tier, slab, mask, long_scan in zip(
                match_stages, tiers, staged.match_slabs, masks, long_scans
            ):
                hits = self._launch(stage, (model, slab))
                from_device.append(hits is not None)
                counts["long_scan_launches"] += long_scan and hits is not None
                if hits is None:
                    device = False
                    hits = self._host_tier_hits(tier, mask)
                tier_hits.append(hits)
            tier_hits = tuple(tier_hits)
            host_operands = sum(from_device)
            post_slab = None
            if post_stage[2] is not None:  # resident: the device answers
                post_slab = staged.post_slab
                host_operands += 1
        # Prefilter confirm (two-level automata): device matcher rows for
        # prefiltered groups are OVER-approximate — re-check positives
        # against the exact DFAs and clear the false ones before anything
        # downstream (post stage, value-cache insert, host post) reads
        # the bits. Host-twin rows are already exact and are skipped.
        if model.prefilter_cols:
            tier_hits = self._confirm_prefilter(tier_hits, tiers, from_device, rec)
        # The post stage takes packed hit rows from EITHER provenance —
        # device matcher output or host-computed numpy — at identical
        # shapes/bit layout, so a mixed window still shares the one post
        # executable.
        with rec.stage("post_enqueue"):
            if post_slab is None:
                device = False
                packed = self._host_post(
                    tier_hits, self._tier_pairs(tiers), numvals, max_phase, cached
                )
            else:
                host_operands += sum(isinstance(hp, np.ndarray) for hp in tier_hits)
                packed = self._launch(post_stage, (model, tier_hits, post_slab))
            counts["host_operands"] += host_operands
        return InFlightBatch(
            out=(packed, tier_hits) if cached is not None else packed,
            n_live=n_requests,
            n_requests=n_requests,
            rejected={},
            miss_keys=miss_keys,
            cache_pop=cached is not None,
            device=device,
            stages=rec,
            arena_lease=staged,
        )

    @staticmethod
    def _tier_rows(tiers, numvals, miss_keys) -> list[int]:
        """Per tier the unique rows its matcher has to match, before
        padding (``tiering.rows``): the value cache's misses, or without
        a cache the unique rows the tier's real pairs read (the
        tensorizers number them from 0)."""
        if miss_keys is not None:
            return [len(keys) for keys in miss_keys]
        out = []
        for t in tiers:
            real = t[8][t[5] < numvals.shape[0]]
            out.append(int(real.max()) + 1 if real.size else 0)
        return out

    def _describe_matchers(self, tiers, masks, keys) -> tuple[bool, ...]:
        """Tell the executable cache how each tier's matcher cuts its
        conv tier to the budget (``compile_cache.executables[].seg_plan``):
        ``tier_seg_plan`` is the function ``match_tier`` traces with, of
        the same statics. Returns, per tier, whether that is the long DFA
        scan."""
        long_scans = []
        for t, mask, key in zip(tiers, masks, keys):
            said = EXEC_CACHE.describe(key)
            if "seg_plan" not in said:  # once a key: the plan walks every column
                plan = tier_seg_plan(self.model, *t[0].shape, mask)
                said = EXEC_CACHE.describe(key, seg_plan=plan.summary() if plan else None)
            long_scans.append((said["seg_plan"] or {}).get("path") == "long")
        return tuple(long_scans)

    def _resolve_launch(self, tiers, numvals, max_phase, masks, cached):
        """What a window of this shape launches: ``(match_stages,
        post_stage, long_scans)``, each stage ``(key, jitted, compiled,
        statics)`` with ``compiled`` None where the executable is not
        resident (lazy mode: the host twin answers that stage);
        ``long_scans`` says of each matcher whether its conv tier was
        traced onto the long DFA scan (``seg_plan.path``).

        Resolved once per (model, window shape): the window's signature
        — shapes and dtypes of its own operands, the masks,
        ``max_phase``, the ``cached`` buckets; a dozen leaves — looks up
        the engine's launch table, and a hit is the whole resolution.
        A miss builds the specs, composes one key per executable from
        the model's kept signature and hands it to every step that used
        to recompute it (each recomputation walked the whole model), and
        enters the table only when every stage is resident: a window
        that a host twin answers is never a hit."""
        from .tier_compile import TIER_COMPILER, spec_key

        sig = shape_signature((tiers, numvals, cached), (masks, max_phase))
        generation, table = self._launch_table
        if generation != EXEC_CACHE.generation:
            generation, table = self._launch_table = (EXEC_CACHE.generation, {})
        plan = table.get(sig)
        EXEC_CACHE.note_launch_plan(hit=plan is not None)
        if plan is not None:
            return plan
        match_specs, post_spec, _pairs = self._tier_specs(
            tiers, numvals, max_phase=max_phase, masks=masks, cached=cached
        )
        specs = match_specs + [post_spec]
        keys = [spec_key(s, self._model_sig) for s in specs]
        self._exec_signatures.update(keys)
        self.compiled.report.exec_signatures = len(self._exec_signatures)
        if self._lazy:
            # Non-blocking: enqueue every missing executable NOW, in
            # ascending cost order, so the pool mints the smallest
            # tier (and the post stage) first — first-verdict-from-
            # device latency is gated on the smallest group's compile.
            ready = {
                k: TIER_COMPILER.ensure(s, k)
                for s, k in sorted(zip(specs, keys), key=lambda sk: sk[0][1])
            }
        else:
            TIER_COMPILER.compile_all(specs, keys)
            ready = dict.fromkeys(keys, True)
        stages = [
            (k, s[2], EXEC_CACHE._lookup(k, count_hit=False) if ready[k] else None, s[4])
            for s, k in zip(specs, keys)
        ]
        long_scans = self._describe_matchers(tiers, masks, keys)
        plan = (stages[:-1], stages[-1], long_scans)
        if all(stage[2] is not None for stage in stages):
            table[sig] = plan
        return plan

    @staticmethod
    def _launch(stage, args: tuple):
        """Enqueue one resolved stage on the device; None where its
        executable is not resident and the host twin has to answer."""
        key, jitted, compiled, statics = stage
        if compiled is None:
            return None
        return EXEC_CACHE.run(key, compiled, jitted, args, statics)

    def _confirm_prefilter(self, tier_hits, tiers, from_device, rec):
        """Confirm device prefilter positives against the exact DFAs.

        The pre-bank columns (``model.prefilter_cols``: (device column,
        gid) pairs) carry verdicts of the APPROXIMATE automata — sound
        over-approximations, so a 0 is final but a 1 may be spurious.
        For every positive row, run the exact DFA on the same
        transformed bytes the device saw (variant-buffer row when the
        pipeline has a host slot, the pipeline applied to the raw row
        otherwise — the ``_host_tier_hits`` convention) and clear the
        bit unless it confirms. Patched rows re-pack to numpy; the post
        stage accepts either provenance, and the value cache then stores
        EXACT bits, so cached replays skip both the matcher and the
        confirm.

        All of a tier's positives whose group the native library handles
        (``NativeConfirm.handled``) go to it in ONE GIL-released call;
        the rest, and every positive of a tier whose native call failed,
        take ``_confirm_python`` — the reference walk, bit-identical.

        Host-twin entries (``from_device`` False) computed exact hits
        already and pass through untouched.

        Two stages of the window's record, per tier: ``prefilter_wait``
        is the host blocked on the matcher's output, ``prefilter_confirm``
        the unpack, the host transforms, the exact walk and the repack."""
        g = int(self.model.e_lg.shape[0])
        cols = self._prefilter_dev_cols
        nc = self._native_confirm
        n_rows = n_hits = n_confirms = n_native = n_errors = 0
        failure = None
        out = list(tier_hits)
        for ti, (hp, tier, dev) in enumerate(zip(tier_hits, tiers, from_device)):
            if not dev:
                continue
            with rec.stage("prefilter_wait"):
                packed = np.asarray(jax.device_get(hp))
            with rec.stage("prefilter_confirm"):
                hits = np.unpackbits(packed, axis=1, count=g)
                n_rows += hits.shape[0] * len(cols)
                rows, ks = np.nonzero(hits[:, cols])
                if rows.size == 0:
                    continue
                n_hits += int(rows.size)
                ok = np.zeros(rows.size, dtype=bool)
                native = nc.handled[ks]
                if native.any():
                    try:
                        ok[native] = nc.run(
                            tier, rows[native], nc.group_of[ks[native]]
                        )
                        n_native += int(native.sum())
                    except RuntimeError as err:
                        n_errors += 1
                        failure = err
                        native[:] = False  # the whole tier is re-walked
                rest = ~native
                if rest.any():
                    ok[rest] = self._confirm_python(tier, rows[rest], ks[rest])
                n_confirms += int(ok.sum())
                if not ok.all():
                    hits[rows[~ok], cols[ks[~ok]]] = 0
                    out[ti] = np.packbits(hits, axis=1)
        if n_rows:
            with self._prefilter_lock:
                self.prefilter_stats["rows"] += n_rows
                self.prefilter_stats["hits"] += n_hits
                self.prefilter_stats["confirms"] += n_confirms
                self.prefilter_stats["false_positives"] += n_hits - n_confirms
                self.prefilter_stats["native_hits"] += n_native
                self.prefilter_stats["native_errors"] += n_errors
                first_failure = n_errors and not self._native_confirm_failed
                self._native_confirm_failed |= bool(n_errors)
            if first_failure:
                log.error(
                    "native prefilter confirm failed; the Python walk confirms"
                    " such windows",
                    failure,
                )
        return tuple(out)

    def _confirm_python(self, tier, rows, ks) -> np.ndarray:
        """The reference confirm: ``DFA.search`` over ``apply_pipeline``
        of each positive ``(rows[j], prefilter column ks[j])``, one
        transform per (pipeline, row). What the native call is held to
        (tests/test_prefilter_confirm_native.py), and what serves where
        the library is absent, older, or lacks an opcode."""
        d = np.asarray(tier[0])
        lg = np.asarray(tier[1])
        vd = np.asarray(tier[6])
        vl = np.asarray(tier[7])
        ok = np.zeros(rows.size, dtype=bool)
        val_cache: dict[tuple[int, int], bytes] = {}
        for j, (i, k) in enumerate(zip(rows.tolist(), ks.tolist())):
            gid = self.model.prefilter_cols[k][1]
            pid = self.compiled.group_pipeline[gid]
            val = val_cache.get((pid, i))
            if val is None:
                slot = int(self.model.host_variant_index[pid])
                if slot >= 0:
                    val = vd[slot, i, : vl[slot, i]].tobytes()
                else:
                    names = list(self.compiled.pipelines[pid])
                    val = apply_pipeline(d[i, : lg[i]].tobytes(), names)
                val_cache[(pid, i)] = val
            ok[j] = self.compiled.groups[gid].dfa.search(val)
        return ok

    def _dense_block_counts(self) -> dict:
        """The plan's dfa-hot and prefilter blocks of the model, counted."""
        kinds = [b.kind for b in self.model.dense_blocks]
        return {
            "dfa_hot_blocks": kinds.count("dfa-hot"),
            "prefilter_blocks": kinds.count("prefilter"),
        }

    def automata_summary(self) -> dict:
        """Automata-tier composition + prefilter counters for stats
        and metrics: which groups run where (the plan's verdict),
        how many dense blocks the dfa-hot and prefilter tiers produced,
        where the dense-DFA blocks are scanned (fused flat bins, or the
        plain bank scan, ``per_bank_kernels``, for a block no bin
        covers), the size of the model they serve
        (compiled rules; the conv tier's output columns, summed over
        its blocks; the runs longer than one conv piece that were split
        into chained pieces, and the groups that hold one; the groups the
        long banks hold, which a tier scans as DFAs where its plan says
        ``long``), and how the
        prefilter's over-approximation is paying off at runtime."""
        from ..ops.segment import conv_n2_cols

        plan = self.automata_plan
        counts = plan.counts()
        model = self.model
        with self._prefilter_lock:
            pstats = dict(self.prefilter_stats)
        return {
            "enabled": plan.enabled,
            "tiers": counts,
            "rules": len(self.rule_meta),
            "segment_columns": sum(conv_n2_cols(sb.spec) for sb in model.segs),
            "segment_splits": sum(t.splits for t in plan.tiers),
            "segment_split_groups": sum(1 for t in plan.tiers if t.splits),
            "segment_long_groups": sum(b.n_groups for b in model.long_banks),
            **self._dense_block_counts(),
            "flat_bins": len(model.flat_banks),
            "flat_slots": sum(fb.n_slots for fb in model.flat_banks),
            "flat_groups": sum(fb.n_groups for fb in model.flat_banks),
            "per_bank_kernels": len(model.banks),
            "prefilter": pstats,
        }

    def tiering_summary(self) -> dict:
        """What the windows' tiering came to, cumulative: windows
        dispatched, matcher tiers launched (one executable call each),
        their cells (unique rows x width, as bucketed), the real
        bytes in them, and the host arrays handed to a launch or a
        ``device_put`` (one transfer each). 1 - real_bytes / cells is
        the share of the matchers' bytes that was padding;
        host_operands / windows reads tiers + 1 (a match slab a tier,
        the post slab), and one more for a tier whose hit rows the
        prefilter confirm repacked. ``long_scan_launches`` counts the
        launches of a matcher whose conv tier was traced onto the long
        DFA scan: 0 while every tier rides the MXU. ``rows`` are the
        unique rows the tiers' matchers had to match and ``rows_padded`` the
        same as bucketed (1 - rows / rows_padded is the share of matcher
        rows that was padding)."""
        return dict(self._tiering)

    def body_summary(self) -> dict:
        """Requests that came with a body, by the processor that read it
        (the native tensorizer's count and the Python extractor's), the
        body bytes received and the bodies that did not parse."""
        total = self._bodies + getattr(self._native, "bodies", 0)
        out: dict = dict(zip(BODY_COUNTERS, (int(v) for v in total)))
        # A native library from before these counters tensorizes bodies
        # and counts none: say so, or the zeros read as "no bodies".
        out["native_uncounted"] = bool(
            getattr(self._native, "available", False)
            and not getattr(self._native, "counts_bodies", True))
        return out

    # -- host twins for not-yet-compiled stages (lazy tier compilation) ------

    def _host_tier_hits(self, tier, mask) -> np.ndarray:
        """Host twin of ``match_tier_packed`` for one tier: walk the
        host fallback's scalar DFAs over the tier's unique rows and
        return the bit-packed hit matrix [U, PB] uint8 in DEVICE column
        order — byte-identical to the device matcher's output, so the
        value cache and the device post stage consume it unchanged."""
        d = np.asarray(tier[0])
        lg = np.asarray(tier[1])
        vd = np.asarray(tier[6])
        vl = np.asarray(tier[7])
        u = d.shape[0]
        g = int(self.model.e_lg.shape[0])
        hits = np.zeros((u, g), dtype=bool)
        hf = self.host_fallback
        for pid, gids, matcher in hf._pipe_groups:
            slot = int(self.model.host_variant_index[pid])
            if slot >= 0:
                # Host-pipeline variant rows were transformed (and
                # re-capped) at tensorize time — reuse them verbatim.
                vals = [
                    vd[slot, i, : vl[slot, i]].tobytes() for i in range(u)
                ]
            else:
                names = list(self.compiled.pipelines[pid])
                vals = [
                    apply_pipeline(d[i, : lg[i]].tobytes(), names)
                    for i in range(u)
                ]
            ph = matcher.search_values(vals)  # [U, len(gids)]
            hits[:, self._dev_col_of[np.asarray(gids)]] = ph
        if mask is not None:
            # Kind-partition parity: the device matcher emits all-False
            # for mask-off blocks (bits 0-61; >= 62 always scanned) —
            # zero the same column spans so packed rows stay identical.
            start = 0
            for bi, n_g in enumerate(self._block_group_counts):
                if bi < 62 and not (mask >> bi) & 1:
                    hits[:, start : start + n_g] = False
                start += n_g
        return np.packbits(hits, axis=1)

    def _host_post(self, tier_hits, pairs, numvals, max_phase, cached) -> np.ndarray:
        """Host twin of ``eval_post_tiered``: unpack each tier's packed
        hit rows (device or host provenance), append cached rows, expand
        to pair rows by uid, drop the padding pair rows (their req_id is
        the out-of-range pad bucket — the host reducer scatters by index
        and must not touch it), permute columns back to ORIGINAL group
        order for the fallback's link tables, and run its NumPy
        post-match. The packed verdict layout matches ``_pack_verdicts``
        bit for bit."""
        g = int(self.model.e_lg.shape[0])
        rows, k1s, k2s, k3s, rids = [], [], [], [], []
        for ti, (hp, (k1, k2, k3, rid, uid)) in enumerate(zip(tier_hits, pairs)):
            hu = np.unpackbits(
                np.asarray(jax.device_get(hp)), axis=1, count=g
            ).astype(bool)
            if cached is not None and cached[ti] is not None:
                cu = np.unpackbits(
                    np.asarray(cached[ti]), axis=1, count=g
                ).astype(bool)
                hu = np.concatenate([hu, cu], axis=0)
            rows.append(hu[np.asarray(uid)])
            k1s.append(np.asarray(k1))
            k2s.append(np.asarray(k2))
            k3s.append(np.asarray(k3))
            rids.append(np.asarray(rid))
        hits = np.concatenate(rows, axis=0)
        k1 = np.concatenate(k1s)
        k2 = np.concatenate(k2s)
        k3 = np.concatenate(k3s)
        rid = np.concatenate(rids)
        real = rid < numvals.shape[0]
        out = self.host_fallback._post_match(
            hits[real][:, self._dev_col_of],
            k1[real],
            k2[real],
            k3[real],
            rid[real],
            np.asarray(numvals),
            max_phase,
        )
        return self._pack_verdicts_np(out)

    @staticmethod
    def _pack_verdicts_np(out) -> np.ndarray:
        """NumPy mirror of ``models/waf_model._pack_verdicts`` — same
        [B, 3 + nw + C] int32 layout (``unpack_compact`` reads both via
        a raw byte view, so host- and device-packed arrays decode
        identically)."""
        b = out["status"].shape[0]
        head = np.stack(
            [
                out["interrupted"].astype(np.int32),
                out["status"].astype(np.int32),
                out["rule_index"].astype(np.int32),
            ],
            axis=1,
        )
        bits = np.packbits(out["matched"].astype(np.uint8), axis=1)
        pad = (-bits.shape[1]) % 4
        if pad:
            bits = np.pad(bits, ((0, 0), (0, pad)))
        words = np.ascontiguousarray(bits).view(np.int32)
        return np.concatenate(
            [head, words, out["scores"].astype(np.int32)], axis=1
        )

    def _verdicts_from_tiers(
        self,
        tiers,
        numvals,
        n_requests: int,
        max_phase: int = 2,
        masks=None,
        cached=None,
        miss_keys=None,
    ) -> list[Verdict]:
        return self.collect(
            self._dispatch_tiers(
                tiers,
                numvals,
                n_requests,
                max_phase=max_phase,
                masks=masks,
                cached=cached,
                miss_keys=miss_keys,
            )
        )

    def _decode_packed(self, packed, n_requests: int) -> list[Verdict]:
        """Batch NumPy decode of the packed verdict array: one nonzero
        pass over the whole matched matrix instead of a per-request
        ``np.flatnonzero`` loop, so the collect stage stays O(batch)
        host work and cannot become the pipeline's serial bottleneck."""
        from ..models.waf_model import matched_id_lists, unpack_compact

        head, matched, scores = unpack_compact(
            packed, self.model.n_rules, self.model.n_counters
        )
        id_lists = matched_id_lists(
            matched, self._rule_ids, self._n_real_rules, n_requests
        )
        head_rows = head[:n_requests].tolist()
        score_rows = scores[:n_requests].tolist()
        counters = self._public_counters
        verdicts: list[Verdict] = []
        for i in range(n_requests):
            interrupted, status, ridx = head_rows[i]
            row = score_rows[i]
            verdicts.append(
                Verdict(
                    interrupted=bool(interrupted),
                    status=int(status),
                    rule_id=int(self._rule_ids[ridx]) if ridx >= 0 else None,
                    matched_ids=id_lists[i],
                    scores={name: row[c] for c, name in counters},
                )
            )
        return verdicts

    def evaluate_one(self, request: HttpRequest) -> Verdict:
        return self.evaluate([request])[0]

    # -- AOT pre-warm --------------------------------------------------------

    def batch_signature(self, requests: list[HttpRequest], max_phase: int = 2):
        """The shape signatures the given batch would dispatch under —
        the tuple of per-stage executable-cache keys (one per tier
        matcher plus the post stage; engine/compile_cache.py). Two
        engines whose signatures match share every compiled executable."""
        from .tier_compile import spec_key

        tiers, numvals, masks, cached, _mkeys, lease = self._batch_tensors(
            requests
        )
        match_specs, post_spec, _pairs = self._tier_specs(
            tiers, numvals, max_phase=max_phase, masks=masks, cached=cached
        )
        if lease is not None:
            lease.release()  # signature only reads shapes; no dispatch
        return tuple(spec_key(s) for s in match_specs + [post_spec])

    def _batch_tensors(self, requests: list[HttpRequest]):
        """Tensorize + tier one request batch. Returns ``(tiers, numvals,
        masks, cached, miss_keys, lease)`` — lease is the staging-arena
        lease on the tiered native path (the caller releases it once the
        device step has consumed the buffers) and None elsewhere."""
        # getattr: tests stub ``_native`` with bare objects that only
        # carry ``available`` to force the Python fallback.
        if getattr(self._native, "tiered", False):
            from ..native import serialize_requests

            return self._native.tier_blob(
                serialize_requests(requests),
                len(requests),
                self._kind_block_lut,
                self.value_cache,
            )
        if self._native.available:
            tensors = self._native.tensorize(requests)
        else:
            extractions = [self.extractor.extract(r) for r in requests]
            tensors = self._tensorize(extractions)
        return self.tier_cached(tensors) + (None,)

    def prewarm(self, requests: list[HttpRequest] | None = None) -> dict:
        """AOT-lower and pre-compile this engine's executable for the
        given batch's shape signature WITHOUT executing it — the
        ``fallback → promoted`` transition runs this off the serving
        path. The warmed signature is the ONE dispatch site
        (``_dispatch_tiers``) that both the synchronous path and the
        pipelined ``prepare``/``collect`` path ride, so promotion never
        eats a first-dispatch stall on either: after prewarm, the
        batcher's first pipelined window is a pure executable-cache hit
        (tests/test_pipeline.py asserts the zero-miss invariant).
        Scope is exactly the GIVEN batch's bucketed signature: a
        production-size batch lands in different row buckets and
        compiles on its first dispatch unless it was prewarmed too —
        set ``CKO_PREWARM_BATCH`` to a representative batch size to have
        the promotion probe additionally warm that signature with
        synthetic varied traffic (costs a full compile before promotion;
        the persistent disk cache makes repeat processes cheap).
        Returns ``{"compiled": bool, "wall_s": float}``."""

        from .tier_compile import TIER_COMPILER, spec_key

        if requests is None:
            requests = [warmup_request()]
        t0 = time.perf_counter()
        compiled = False
        batches = [requests]
        warm_n = int(_os.environ.get("CKO_PREWARM_BATCH", "0"))
        if warm_n > 1:
            from ..corpus import synthetic_requests

            batches.append(synthetic_requests(warm_n, attack_ratio=0.1, seed=7))
        for batch in batches:
            tiers, numvals, masks, cached, _mkeys, lease = self._batch_tensors(
                batch
            )
            match_specs, post_spec, _pairs = self._tier_specs(
                tiers, numvals, max_phase=2, masks=masks, cached=cached
            )
            specs = match_specs + [post_spec]
            keys = [spec_key(s, self._model_sig) for s in specs]
            self._describe_matchers(tiers, masks, keys)
            compiled = TIER_COMPILER.compile_all(specs, keys) > 0 or compiled
            if lease is not None:
                lease.release()  # AOT compile only; nothing dispatched
        return {"compiled": compiled, "wall_s": time.perf_counter() - t0}

    # -- phase-split serving -------------------------------------------------

    def _evaluate_extractions(
        self, extractions: list, max_phase: int
    ) -> list[Verdict]:
        tensors = self._tensorize(extractions)
        tiers, numvals, masks, cached, mkeys = self.tier_cached(tensors)
        return self._verdicts_from_tiers(
            tiers,
            numvals,
            len(extractions),
            max_phase=max_phase,
            masks=masks,
            cached=cached,
            miss_keys=mkeys,
        )

    def evaluate_phased(self, requests: list[HttpRequest]) -> list[Verdict]:
        """Two-pass phase-split evaluation (reference data-plane semantics,
        SURVEY §3.4): phase-1 rules decide on headers BEFORE the body is
        read — pass 1 never touches ``req.body`` (no parse, no tensorize);
        only requests that survive run the full request phases."""
        if not requests:
            return []
        pass1 = [
            self.extractor.extract(r, phase1_only=True) for r in requests
        ]
        early = self._evaluate_extractions(pass1, max_phase=1)
        survivors = [i for i, v in enumerate(early) if not v.interrupted]
        if survivors:
            full = self.evaluate([requests[i] for i in survivors])
            for i, verdict in zip(survivors, full):
                early[i] = verdict
        return early

    def evaluate_response(self, request: HttpRequest, response) -> Verdict:
        """Phases 3/4: evaluate the upstream response (plus the request
        context) — RESPONSE_STATUS/HEADERS/STATUS_LINE and, when
        ``SecResponseBodyAccess On``, RESPONSE_BODY up to
        ``SecResponseBodyLimit``."""
        ex = self.extractor.extract(request, response=response)
        return self._evaluate_extractions([ex], max_phase=4)[0]


# -- bulk fast path ----------------------------------------------------------

def _engine_evaluate_bulk_json(self, body: bytes):
    """Evaluate a bulk JSON payload entirely through the native ingest:
    C++ parses the JSON, extracts targets, applies host transforms and
    host ops, and packs rows; Python only tiers, dispatches the device
    step, and decodes verdicts. Returns (verdicts, request_blob) or
    None when the native path is unavailable or the JSON is malformed
    (caller falls back to the schema-error-reporting object path)."""
    if not self._native.available:
        return None
    parsed = self._native.tensorize_json(body)
    if parsed is None:
        return None
    tensors, n_req, blob = parsed
    if n_req == 0:
        return [], blob
    tiers, numvals, masks, cached, mkeys = self.tier_cached(tensors)
    verdicts = self._verdicts_from_tiers(
        tiers, numvals, n_req, masks=masks, cached=cached, miss_keys=mkeys
    )
    prog = self.compiled.program
    if prog.request_body_access and prog.request_body_limit_action == "Reject":
        # Parity with the object path: SecRequestBodyLimitAction Reject
        # interrupts over-limit bodies with 413 (the C++ tensorizer
        # truncates at the limit; the blob keeps full lengths). A
        # phase-1 interruption wins — the limit fires at body ingest,
        # after phase 1 already ran (Coraza ordering).
        from ..native import blob_over_limit

        for i in blob_over_limit(blob, prog.request_body_limit):
            if i < n_req and not (
                verdicts[i].interrupted
                and self._rule_phase.get(verdicts[i].rule_id, 2) <= 1
            ):
                verdicts[i] = Verdict(interrupted=True, status=413, rule_id=None)
    return verdicts, blob


WafEngine.evaluate_bulk_json = _engine_evaluate_bulk_json
