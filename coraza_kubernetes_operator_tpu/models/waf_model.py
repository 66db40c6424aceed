"""Device WAF model: CompiledRuleSet → pytree + jittable batch evaluation.

Evaluation pipeline (all shape-static, one ``jit`` trace per bucket shape):

1. transform: apply each distinct device transform pipeline to the target
   buffer (host-only pipelines arrive pre-transformed as variant buffers);
2. match: scan every DFA bank over its pipeline's buffer → per-target,
   per-group hits;
3. incidence: two bool-table gathers resolve which rules see which targets
   (variable include/exclude semantics);
4. reduce: scatter-max targets → requests, AND chain links, matmul match
   flags into anomaly-score counters, evaluate threshold links;
5. verdict: first-match-wins disruptive decision honoring phases and
   SecRuleEngine mode.

The reference delegates all of this to coraza-proxy-wasm per request
(SURVEY §3.4); here it is one fused batch computation.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.ruleset import (
    CompiledRuleSet,
    DEC_ALLOW,
    DEC_DENY,
    DEC_DROP,
    DEC_REDIRECT,
    LINK_ALWAYS,
    LINK_COUNTER,
    LINK_NEVER,
    LINK_NUMERIC,
    LINK_STRING,
)
from ..compiler.automata_plan import cut_hot_blocks
from ..compiler.segments import plan_segments
from ..ops.dfa import DFABank, scan_dfa_bank, stack_dfas
from ..ops.dfa_flat import build_flat_bank, plan_flat_bins, scan_flat_bank
from ..ops.segment import SegmentBlock, build_segment_block, match_segment_block
from ..ops.transforms import apply_device_pipeline
from .slab import unpack_match_slab, unpack_post_slab

_BIG = np.int32(2**31 - 1)

# The conv tier's element budget: how many match-bitmap elements (rows *
# (L+2) positions * N2 conv columns: the bf16 conv output of one launch)
# may be alive at once. ``segment_tier_hits`` cuts a tier to it per
# traced shape, from shapes alone (``plan_segment_tier``), in as few
# pieces as it allows: the whole bitmap under it runs direct (one conv a
# block and ONE pass of the chains over all rows: a chain operation costs
# by the rows it reads, so passes over row chunks move the same bytes and
# add the loop's own slices and copies, 10.9 of the 60.9 ms of crs-lite's
# ``512x512`` launch under the 2^27 this budget used to be, PR 46); over
# it the rows are CHUNKED (the conv matchers inside a ``lax.map`` over
# row blocks: the round-4 trace showed the 19k-row short tier falling off
# the conv tier into 26 serializing long-bank DFA scans, ~60% of the
# whole CRS-scale step, because the only options were one giant bitmap or
# the scan fallback); where not even eight rows of all columns fit, the
# columns are TILED as well, along group boundaries (PR 43): all the rows
# in one chunk of tiles while they fit, then row chunks of tiles. The DFA
# long-bank fallback remains for the case ONE row of ONE tile exceeds the
# budget, and, on a backend that is no TPU, for a tier whose rows do not
# fit one chunk of tiles (``_scan_past_one_chunk``: the CPU pays the
# conv's arithmetic in full, the chip the scan's serial steps).
#
# The budget follows what the code can observe, the device's memory
# (``seg_chunk_budget``): a launch's bf16 conv output may take one part in
# ``_SEG_HBM_SHARE`` of the device's ``memory_stats()["bytes_limit"]``
# (a v5e says 16,909,336,064 bytes: 1,056,833,504 elements; every shape
# a benchmark cell serves is direct there, and two such launches in
# flight, one a lane, stay under half the device by the compiler's own
# count of their temporaries: PERF.md §6, PR 46). A device that reports
# no memory (XLA:CPU: tier-1) keeps ``_SEG_CHUNK_ELEMS_NO_STATS``, the
# 2^27 every plan was cut to until PR 46, so no CPU plan moved.
# ``_SEG_CHUNK_ELEMS`` is the override the tests and
# ``hack/seg_plan_equality.py`` patch; nothing reads the environment.
#
# CKO_SEG_BITMAP_ELEMENTS is NOT a budget of the bitmap: it only decides
# whether the long banks are built (``build_model``) and used
# (``long_ok``). 0 builds none (saves their HBM if length buckets are
# known-small): a tier then runs direct where one row of the widest group
# is over the budget.
import os as _os

_SEG_BITMAP_ELEMS = int(_os.environ.get("CKO_SEG_BITMAP_ELEMENTS", str(2**30)))
_SEG_CHUNK_ELEMS_NO_STATS = 2**27
_SEG_HBM_SHARE = 8
_SEG_CHUNK_ELEMS: int | None = None


def seg_chunk_budget(bytes_limit: int | None) -> int:
    """The conv tier's element budget on a device of ``bytes_limit`` bytes
    (None or 0: the device reports no memory)."""
    if not bytes_limit:
        return _SEG_CHUNK_ELEMS_NO_STATS
    return bytes_limit // _SEG_HBM_SHARE // 2  # bf16: two bytes an element


@lru_cache(maxsize=None)
def _device_bytes_limit() -> int | None:
    """``bytes_limit`` of the device a launch runs on (every local device
    of a host is the same chip), None where it reports no memory. Asked
    once a process: at the first plan, never at import."""
    return (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")


def _seg_chunk_elems() -> int:
    if _SEG_CHUNK_ELEMS is not None:
        return _SEG_CHUNK_ELEMS
    return seg_chunk_budget(_device_bytes_limit())


def _state_bucket(n_states: int) -> int:
    """Padded state-count bucket for bank stacking (shared by the DFA
    tier, the long-buffer fallback, and the rule-sharded layout)."""
    return next(b for b in _STATE_BUCKETS if n_states <= b)

# Size buckets for DFA banks (n_states ceiling): groups whose tables fit the
# same bucket share one padded bank — bounded padding waste, few fused scans.
# COARSE lattice (shape quantization): buckets GROUP banks — stack_dfas
# still pads each bank to its largest member, so coarsening trades some
# small-member padding inside a bank for far fewer distinct bank
# layouts: fewer executables to compile cold, more EXEC_CACHE sharing
# across similar-size rulesets. Hopcroft minimization
# (compiler/re_dfa.py) already shrank the state counts feeding this
# lattice, so the octave-per-step resolution loss is cheap.
_STATE_BUCKETS = (32, 256, 2048, 16384, 65536)


@dataclass(frozen=True)
class DenseBlock:
    """One dense-DFA block of the model, a static record: the pipeline
    its DFAs read and how many they are (what a trace needs: a skipped
    block's zero columns, an uncovered block's buffer), and, host-side
    only, its kind (``nfa``: an exact state bucket; ``dfa-hot``;
    ``prefilter``: over-approximations the engine confirms on the host)
    and the state count of its widest DFA (``block_cost``). The
    host-side two stay out of equality and hash, and so out of the
    executable cache's key (``WafModel.tree_flatten``'s aux): no traced
    code reads them."""

    pipeline: int
    groups: int
    kind: str = field(default="", compare=False)
    states: int = field(default=0, compare=False)


@jax.tree_util.register_pytree_node_class
@dataclass
class WafModel:
    """Pytree of device arrays + static metadata (hashable aux)."""

    # The stacked DFAs of the dense blocks NO flat bin covers (one DFA of
    # such a block is past the bins' VMEM plan, ``plan_flat_bins``);
    # ``bank_blocks`` names each one's block. A covered block has no bank:
    # its tables live in its bins alone.
    banks: list[DFABank]
    # Conv-segment tier: groups whose regex decomposes exactly into
    # fixed-length segments + gaps match here (one MXU conv for all
    # positions, ``ops/segment.py``); only the rest scan DFA banks.
    segs: list[SegmentBlock]
    # link arrays [Rl]
    ltype: jnp.ndarray
    lneg: jnp.ndarray
    lgroup: jnp.ndarray
    lnumvar: jnp.ndarray
    lcmp: jnp.ndarray
    lcmparg: jnp.ndarray
    lcounter: jnp.ndarray
    # incidence [K+1, Rl]
    inc: jnp.ndarray
    exc: jnp.ndarray
    # matmul-formulated constants (gathers serialize on TPU; these ride MXU)
    e_lg: jnp.ndarray  # [G, Rl] int8 one-hot of lgroup
    m_count: jnp.ndarray  # [Rl, Rr] int8: multiplicity of link l in rule r
    link_count: jnp.ndarray  # [Rr] int32: number of links per rule
    e_numvar: jnp.ndarray  # [NV, Rl] f32 one-hot of lnumvar
    e_counter: jnp.ndarray  # [C, Rl] f32 one-hot of lcounter
    # ctl:ruleRemoveById/ByTag: removal[i, j] = 1 when a match of rule i
    # disables later rule j for the request (order constraint baked in
    # at build). Applied once after the preliminary link pass.
    removal: jnp.ndarray  # [Rr, Rr] int8
    # rule arrays [Rr]
    link_matrix: jnp.ndarray  # [Rr, MX]
    link_mask: jnp.ndarray  # [Rr, MX]
    decision: jnp.ndarray
    status: jnp.ndarray
    order_key: jnp.ndarray
    phase: jnp.ndarray
    # counters
    weights: jnp.ndarray  # [Rr, C]
    counter_base: jnp.ndarray  # [C]
    # Long-buffer fallback: the conv tier materializes a [T, Q, N] match
    # bitmap, which is linear in buffer length — a long-body shape bucket
    # would OOM. Every segment-routed group also keeps its DFA stacked in
    # these banks; eval_waf picks the tier per TRACE (shapes are static),
    # so long buckets stream through the constant-memory scan carry.
    long_banks: list = field(default_factory=list)
    seg_perm: jnp.ndarray | None = None  # [Gs, Gs] one-hot: long order → seg order
    # Flat-slot fused bins (ops/dfa_flat.py): the dense blocks' DFAs as
    # a few fused VMEM-resident scans; match_tier takes a covered block's
    # columns from its bins.
    flat_banks: list = field(default_factory=list)
    # static metadata
    # The dense-DFA blocks, in the column order (after the seg blocks):
    # the exact nfa buckets, then the plan's dfa-hot blocks, then its
    # prefilter blocks. A prefilter block's columns may over-match by
    # design: the engine's dispatch confirms positive rows against the
    # exact automata on the host (prefilter_cols below) before the post
    # stage, so verdicts never change, and a model that holds one must
    # only be evaluated through that confirm path. No dfa-hot or
    # prefilter block unless build_model was handed a plan.
    dense_blocks: tuple = ()
    bank_blocks: tuple = ()  # block index (segs first) per entry of banks
    seg_pipelines: tuple = field(default_factory=tuple)  # pipeline id per seg block
    long_bank_pipelines: tuple = field(default_factory=tuple)
    pipelines: tuple = field(default_factory=tuple)  # names per pipeline id
    pipeline_device: tuple = field(default_factory=tuple)
    host_variant_index: tuple = field(default_factory=tuple)  # pid -> variant slot (-1 device)
    engine_on: bool = True
    detection_only: bool = False
    has_removals: bool = False  # static: skip the removal matmul when empty
    # Remover rule indexes in evaluation (order_key) order — the ctl
    # pass walks them sequentially so a ctl rule removed by an earlier
    # ctl never applies its own removals (Coraza in-order semantics).
    removal_rows: tuple = ()
    # Kind-partitioned matching (static): per matcher block (segs first,
    # then the dense blocks — match_tier's concat order), the tuple of kind ids
    # that can reach any of the block's groups, and a rough relative
    # per-row cost. tier_tensors partitions rows by the set of blocks
    # their kinds can reach; a tier whose mask excludes a block skips
    # its matcher entirely (hits = False is exact: post_match's `rel`
    # gate already resolves those links False for such rows).
    block_kinds: tuple = ()
    block_cost: tuple = ()
    # Static: some rule has BOTH a counter link and nonzero weights (the
    # ctl:ruleRemoveTargetById variants) — post_match then runs a second
    # counter pass so counter-gated rules' own setvars still accumulate.
    two_pass_counters: bool = False
    # Static: block indexes (segs first, then dense_blocks: the column
    # order) whose hit columns come from flat_banks.
    flat_covered: tuple = ()
    # Host-side only: ORIGINAL group id held by each device hit column
    # (the inverse of build_model's remap). The lazy per-tier dispatch
    # uses it to compute host-path tier hits in device column order and
    # to permute them back for the host post-match. Canonicalized out of
    # the aux like block_kinds/block_cost — never read in a trace.
    group_order: tuple = ()
    # Host-side only: (device hit column, original group id) per
    # prefiltered group — the engine's confirm step re-checks positive
    # rows of these columns against the exact DFA. Canonicalized out of
    # the aux like group_order — never read in a trace.
    prefilter_cols: tuple = ()

    def tree_flatten(self):
        leaves = (
            self.banks,
            self.segs,
            self.ltype,
            self.lneg,
            self.lgroup,
            self.lnumvar,
            self.lcmp,
            self.lcmparg,
            self.lcounter,
            self.inc,
            self.exc,
            self.e_lg,
            self.m_count,
            self.link_count,
            self.e_numvar,
            self.e_counter,
            self.removal,
            self.link_matrix,
            self.link_mask,
            self.decision,
            self.status,
            self.order_key,
            self.phase,
            self.weights,
            self.counter_base,
            self.long_banks,
            self.seg_perm,
            self.flat_banks,
        )
        # CANONICAL aux (shape-canonical executable reuse): the aux tuple
        # is the jit/AOT cache key's treedef component, so it must contain
        # ONLY trace-relevant statics. block_kinds/block_cost are host-side
        # planning metadata (tier_tensors' kind clustering) that never
        # enters a trace — carrying their ruleset-specific values here made
        # two same-layout rulesets hash to different executables. They
        # flatten as () placeholders; unflattened copies (the jit-internal
        # reconstruction, device_put round trips) see empty tuples, which
        # no traced code reads. (A dense block's kind and states stay out
        # of the key by ``DenseBlock``'s own equality.)
        aux = (
            self.dense_blocks,
            self.bank_blocks,
            self.seg_pipelines,
            self.long_bank_pipelines,
            self.pipelines,
            self.pipeline_device,
            self.host_variant_index,
            self.engine_on,
            self.detection_only,
            self.has_removals,
            self.removal_rows,
            (),  # block_kinds: host-side only, canonicalized out
            (),  # block_cost: host-side only, canonicalized out
            self.two_pass_counters,
            self.flat_covered,
            (),  # group_order: host-side only, canonicalized out
            (),  # prefilter_cols: host-side only, canonicalized out
        )
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def n_rules(self) -> int:
        return int(self.decision.shape[0])

    @property
    def n_counters(self) -> int:
        return int(self.counter_base.shape[0])


def lgroup_onehot(lgroup: np.ndarray, n_groups: int) -> np.ndarray:
    """[G, Rl] int8 one-hot of each link's group id — the post_match matmul
    constant. Shared with the rule-sharded layout (``parallel/mesh.py``)."""
    e_lg = np.zeros((n_groups, len(lgroup)), dtype=np.int8)
    for i, g in enumerate(lgroup):
        e_lg[g, i] = 1
    return e_lg


def build_model(crs: CompiledRuleSet, automata=None) -> WafModel:
    """Lay out a CompiledRuleSet as device arrays. Groups are re-ordered so
    each block's groups are contiguous; links are rewritten accordingly.

    Routing: each group first tries the exact conv-segment decomposition
    (``compiler/segments.py``) — those match on the MXU conv tier; the
    rest bucket into dense-DFA blocks by state count. Global group order
    (and the lgroup remap) is: segment blocks sorted by pipeline id, then
    the exact DFA buckets sorted by (pipeline, bucket), then the dfa-hot
    blocks, then the prefilter buckets.

    ``automata`` (``compiler/automata_plan.AutomataPlan`` or None) turns
    on the two-level automata layout: the plan's "dfa-hot" groups leave
    the state buckets for blocks of their own and its "prefiltered"
    groups are REPLACED on device by their small over-approximating
    automata (the "prefilter" blocks + ``prefilter_cols``).
    The default (None) keeps every group exact — direct ``eval_waf*``
    callers and the sharded path (``parallel/mesh.py``) never see an
    approximate column; only ``engine.waf.WafEngine`` passes a plan, and
    its dispatch confirms prefilter positives before the post stage."""
    seg_groups: dict[int, list[tuple[int, object]]] = {}
    buckets: dict[tuple[int, int], list[int]] = {}
    hot_buckets: dict[tuple[int, int], list[int]] = {}
    pre_buckets: dict[tuple[int, int], list[int]] = {}
    approx_of: dict[int, object] = {}
    tier_of = (
        {t.gid: t for t in automata.tiers} if automata is not None else {}
    )
    for gid, grp in enumerate(crs.groups):
        pid = crs.group_pipeline[gid]
        plan = plan_segments(grp.dfa.ast)
        if plan is not None:
            seg_groups.setdefault(pid, []).append((gid, plan))
            continue
        entry = tier_of.get(gid)
        if entry is not None and entry.kind == "dfa-hot":
            hot_buckets.setdefault(
                (pid, _state_bucket(grp.dfa.n_states)), []
            ).append(gid)
            continue
        if entry is not None and entry.kind == "prefiltered" and entry.approx is not None:
            approx_of[gid] = entry.approx
            pre_buckets.setdefault(
                (pid, _state_bucket(entry.approx.n_states)), []
            ).append(gid)
            continue
        buckets.setdefault((pid, _state_bucket(grp.dfa.n_states)), []).append(gid)

    remap = np.zeros(max(1, len(crs.groups)), dtype=np.int64)
    next_new = 0
    segs: list[SegmentBlock] = []
    seg_pipelines: list[int] = []
    for pid in sorted(seg_groups):
        items = seg_groups[pid]
        segs.append(build_segment_block([plan for _, plan in items]))
        seg_pipelines.append(pid)
        for g, _ in items:
            remap[g] = next_new
            next_new += 1

    # The dense-DFA blocks, listed once, in the column order: the exact
    # nfa buckets, the dfa-hot blocks (a (pipeline, bucket) population cut
    # by ``cut_hot_blocks``; one piece == one maskable block), the
    # prefilter buckets (the plan's over-approximating automata; their
    # columns over-match by design, and prefilter_cols records which
    # device columns need the engine's exact host confirm). Each is its
    # kind, its pipeline, its members' original group ids and their DFAs.
    blocks: list[tuple[str, int, list[int], list]] = []
    for (pid, _bucket), gids in sorted(buckets.items()):
        blocks.append(("nfa", pid, gids, [crs.groups[g].dfa for g in gids]))
    for (pid, _bucket), gids in sorted(hot_buckets.items()):
        for cut in cut_hot_blocks([crs.groups[g].dfa for g in gids]):
            members = [gids[i] for i in cut]
            blocks.append(("dfa-hot", pid, members, [crs.groups[g].dfa for g in members]))
    for (pid, _bucket), gids in sorted(pre_buckets.items()):
        blocks.append(("prefilter", pid, gids, [approx_of[g] for g in gids]))
    prefilter_cols: list[tuple[int, int]] = []
    for kind, _pid, gids, _dfas in blocks:
        for g in gids:
            if kind == "prefilter":
                prefilter_cols.append((next_new, g))
            remap[g] = next_new
            next_new += 1
    dense_blocks = tuple(
        DenseBlock(pid, len(dfas), kind, max(d.n_states for d in dfas))
        for kind, pid, _gids, dfas in blocks
    )

    # Flat-slot fused bins (ops/dfa_flat.py): every dense block is offered
    # to the flat planner, and the blocks it accepts collapse into a few
    # VMEM-resident fused kernels that cost their real states. It rejects
    # a block only when ONE exact DFA of it overflows the bins' VMEM plan
    # (thousands of states that the prefilter could not front): such a
    # block alone gets a ``DFABank``, which has no dense table at that
    # size, and match_tier scans it with the gather scan of ops/dfa.py.
    # Column order is untouched: a bin's pieces carry (block, g_lo, g_hi)
    # and match_tier stitches by block.
    n_segs_blocks = len(segs)
    bins, rejected = plan_flat_bins(
        [
            (n_segs_blocks + i, pid, dfas)
            for i, (_kind, pid, _gids, dfas) in enumerate(blocks)
        ]
    )
    flat_banks = [build_flat_bank(bn) for bn in bins]
    bank_blocks = tuple(sorted(rejected))
    banks = [stack_dfas(blocks[blk - n_segs_blocks][3]) for blk in bank_blocks]
    flat_covered = tuple(
        blk
        for blk in range(n_segs_blocks, n_segs_blocks + len(blocks))
        if blk not in rejected
    )

    # Long-buffer fallback banks: every segment-routed group's DFA,
    # bucketed by state count like the normal banks. Their concatenated
    # column order differs from the seg-column order, so seg_perm maps
    # it back with one one-hot matmul (a minor-axis gather would
    # serialize on TPU).
    long_banks: list[DFABank] = []
    long_bank_pipelines: list[int] = []
    long_order: list[int] = []
    if _SEG_BITMAP_ELEMS > 0:  # 0 = fallback disabled, skip the HBM cost
        long_buckets: dict[tuple[int, int], list[int]] = {}
        for pid in sorted(seg_groups):
            for gid, _plan in seg_groups[pid]:
                key = (pid, _state_bucket(crs.groups[gid].dfa.n_states))
                long_buckets.setdefault(key, []).append(gid)
        for (pid, _bucket), gids in sorted(long_buckets.items()):
            long_banks.append(stack_dfas([crs.groups[g].dfa for g in gids]))
            long_bank_pipelines.append(pid)
            long_order.extend(gids)
    n_seg_groups = sum(len(v) for v in seg_groups.values())
    seg_perm = None
    if long_order:
        perm = np.zeros((len(long_order), n_seg_groups), dtype=np.int8)
        for j, gid in enumerate(long_order):
            perm[j, remap[gid]] = 1  # seg groups hold remap ids [0, Gs)
        seg_perm = jnp.asarray(perm)

    # Host pipeline variant slots.
    host_variant_index = []
    slot = 0
    for dev in crs.pipeline_device:
        if dev:
            host_variant_index.append(-1)
        else:
            host_variant_index.append(slot)
            slot += 1

    rl = max(1, len(crs.links))
    ltype = np.full(rl, LINK_NEVER, dtype=np.int32)
    lneg = np.zeros(rl, dtype=bool)
    lgroup = np.zeros(rl, dtype=np.int32)
    lnumvar = np.zeros(rl, dtype=np.int32)
    lcmp = np.zeros(rl, dtype=np.int32)
    lcmparg = np.zeros(rl, dtype=np.int32)
    lcounter = np.zeros(rl, dtype=np.int32)
    k = crs.vocab.n_kinds
    inc = np.zeros((k, rl), dtype=bool)
    exc = np.zeros((k, rl), dtype=bool)
    for i, link in enumerate(crs.links):
        ltype[i] = link.link_type
        lneg[i] = link.negated
        if link.link_type == LINK_STRING:
            lgroup[i] = remap[link.group]
            for kid in link.include_kinds:
                inc[kid, i] = True
            for kid in link.exclude_kinds:
                exc[kid, i] = True
        lnumvar[i] = max(0, link.numvar)
        lcmp[i] = link.cmp
        lcmparg[i] = link.cmp_arg
        lcounter[i] = max(0, link.counter)

    rr = max(1, len(crs.rules))
    mx = max([len(r.link_ids) for r in crs.rules] or [1])
    link_matrix = np.zeros((rr, mx), dtype=np.int32)
    link_mask = np.zeros((rr, mx), dtype=bool)
    decision = np.zeros(rr, dtype=np.int32)
    status = np.zeros(rr, dtype=np.int32)
    order_key = np.full(rr, 2**31 - 1, dtype=np.int32)
    phase = np.full(rr, 99, dtype=np.int32)
    for i, rule in enumerate(crs.rules):
        for j, lid in enumerate(rule.link_ids):
            link_matrix[i, j] = lid
            link_mask[i, j] = True
        decision[i] = rule.decision
        status[i] = rule.status
        order_key[i] = rule.order_key
        phase[i] = rule.phase

    weights = crs.weights if crs.weights.size else np.zeros((rr, 1), dtype=np.int32)
    if weights.shape[0] != rr:
        padded = np.zeros((rr, weights.shape[1]), dtype=np.int32)
        padded[: weights.shape[0]] = weights
        weights = padded

    # Matmul-formulated constants for post_match.
    e_lg = lgroup_onehot(lgroup, max(1, len(crs.groups)))
    m_count = np.zeros((rl, rr), dtype=np.int8)
    link_count = np.zeros(rr, dtype=np.int32)
    for i, rule in enumerate(crs.rules):
        link_count[i] = len(rule.link_ids)
        for lid in rule.link_ids:
            m_count[lid, i] += 1
    # numvar/counter selection as one-hot matmul operands: the gather
    # forms numvals[:, lnumvar] / counters[:, lcounter] produce [B, Rl]
    # outputs through XLA's serializing TPU gather (profiled at ~40% of
    # post_match); the contraction rides the MXU instead, split into
    # 12-bit halves at eval time so it is exact for the FULL int32 range
    # (body-length scalars are attacker-controlled and exceed 2^24).
    nv = max(1, crs.numvars.n_vars if hasattr(crs, "numvars") else 1)
    n_counters = weights.shape[1]
    e_numvar = np.zeros((nv, rl), dtype=np.float32)
    e_counter = np.zeros((n_counters, rl), dtype=np.float32)
    for i in range(rl):
        e_numvar[min(lnumvar[i], nv - 1), i] = 1.0
        e_counter[min(lcounter[i], n_counters - 1), i] = 1.0

    # ctl:ruleRemoveById/ByTag removal matrix: a match of rule i disables
    # every LATER rule j whose id/tag it names (per-transaction rule
    # removal — reference: Coraza ctl actions; CRS exception idiom).
    removal = np.zeros((rr, rr), dtype=np.int8)
    has_removals = False
    for i, r in enumerate(crs.rules):
        if not r.ctl_remove_ranges and not r.ctl_remove_tags:
            continue
        for j, r2 in enumerate(crs.rules):
            if j == i or r2.order_key <= r.order_key:
                continue
            hit = any(lo <= r2.rule_id <= hi for lo, hi in r.ctl_remove_ranges)
            if not hit and r.ctl_remove_tags:
                hit = any(t in r2.tags for t in r.ctl_remove_tags)
            if hit:
                removal[i, j] = 1
                has_removals = True
    removal_rows = tuple(
        sorted(
            (i for i in range(rr) if i < len(crs.rules) and removal[i].any()),
            key=lambda i: crs.rules[i].order_key,
        )
    )

    # Kind-partitioned matching constants: which kinds can reach each
    # matcher block (union of the include sets of every string link on
    # any of the block's groups), and a rough relative per-row cost by
    # formulation (conv / fused flat scan / serializing gather scan).
    # Only the RANKING matters — tier_tensors uses the
    # costs to cluster row partitions, never as absolute time.
    from ..ops.segment import conv_n2_cols

    gkind_sets: list[set[int]] = [set() for _ in range(max(1, len(crs.groups)))]
    for link in crs.links:
        if link.link_type == LINK_STRING and link.group >= 0:
            gkind_sets[link.group].update(link.include_kinds)

    def kinds_of(gids) -> tuple[int, ...]:
        return tuple(sorted(set().union(*(gkind_sets[g] for g in gids))))

    block_kinds = [
        kinds_of(gid for gid, _plan in seg_groups[pid]) for pid in sorted(seg_groups)
    ] + [kinds_of(gids) for _kind, _pid, gids, _dfas in blocks]
    block_cost = [float(conv_n2_cols(seg.spec)) for seg in segs]
    for blk, db in enumerate(dense_blocks, start=n_segs_blocks):
        if blk in rejected:
            block_cost.append(1000.0 * db.groups)  # the gather scan serializes
        else:
            block_cost.append(0.5 * db.states * db.groups)  # fused flat scan
    # Inverse of remap: original group id per device hit column (host
    # metadata for the lazy host-tier path — see WafModel.group_order).
    n_g = len(crs.groups)
    order_arr = np.zeros(n_g, dtype=np.int64)
    order_arr[remap[:n_g]] = np.arange(n_g, dtype=np.int64)
    group_order = tuple(int(x) for x in order_arr)

    w_np = np.asarray(weights)
    two_pass_counters = any(
        any(crs.links[l].link_type == LINK_COUNTER for l in r.link_ids)
        and w_np[i].any()
        for i, r in enumerate(crs.rules)
    )

    return WafModel(
        banks=banks,
        segs=segs,
        ltype=jnp.asarray(ltype),
        lneg=jnp.asarray(lneg),
        lgroup=jnp.asarray(lgroup),
        lnumvar=jnp.asarray(lnumvar),
        lcmp=jnp.asarray(lcmp),
        lcmparg=jnp.asarray(lcmparg),
        lcounter=jnp.asarray(lcounter),
        inc=jnp.asarray(inc),
        exc=jnp.asarray(exc),
        e_lg=jnp.asarray(e_lg),
        m_count=jnp.asarray(m_count),
        link_count=jnp.asarray(link_count),
        e_numvar=jnp.asarray(e_numvar),
        e_counter=jnp.asarray(e_counter),
        removal=jnp.asarray(removal),
        link_matrix=jnp.asarray(link_matrix),
        link_mask=jnp.asarray(link_mask),
        decision=jnp.asarray(decision),
        status=jnp.asarray(status),
        order_key=jnp.asarray(order_key),
        phase=jnp.asarray(phase),
        weights=jnp.asarray(weights.astype(np.int32)),
        counter_base=jnp.asarray(
            crs.counter_base if crs.counter_base.size else np.zeros(1, np.int32)
        ),
        long_banks=long_banks,
        seg_perm=seg_perm,
        flat_banks=flat_banks,
        dense_blocks=dense_blocks,
        bank_blocks=bank_blocks,
        seg_pipelines=tuple(seg_pipelines),
        long_bank_pipelines=tuple(long_bank_pipelines),
        pipelines=tuple(tuple(p) for p in crs.pipelines),
        pipeline_device=tuple(crs.pipeline_device),
        host_variant_index=tuple(host_variant_index),
        engine_on=crs.engine_mode != "Off",
        detection_only=crs.engine_mode == "DetectionOnly",
        has_removals=has_removals,
        removal_rows=removal_rows,
        block_kinds=tuple(block_kinds),
        block_cost=tuple(block_cost),
        two_pass_counters=two_pass_counters,
        flat_covered=flat_covered,
        group_order=group_order,
        prefilter_cols=tuple(prefilter_cols),
    )


@dataclass(frozen=True)
class SegTierPlan:
    """How one traced shape's conv tier is cut to ``budget`` elements
    (``seg_chunk_budget`` of the device's memory, or the override).

    ``path``: ``direct`` (one conv a block over all rows), ``rows``
    (``lax.map`` over row chunks), ``tiles`` (column tiles inside each row
    chunk) or ``long`` (the DFA long-bank scan). ``tiles`` lists the
    column tiles of a chunk, ``(block, g0, g1, conv columns)``, in the
    order they run; the other paths run whole blocks. ``reach_gaps``:
    the unbounded class gaps of those tiles' suffix structures that
    ``ops/segment.py`` runs as reachability matmuls, by the size of a
    structure's block over a chunk's rows (a chunk's worth: a ``lax.map``
    body counts once, as in ``device_ops``). ``conv_passes`` and
    ``conv_fill`` say how far the tiles' convolutions fill the MXU's
    depth (``ops/segment.py:conv_tap_packing``): the passes of the array's
    depth the tiles' convs make an output tile, summed (taps x 128-deep
    slices of a tap's contraction: W a block while a tap contracts over its
    C channels alone, ``ceil(W / (128 // C))`` with the taps packed), and
    the share of the depth a pass fills, weighted by the tiles' columns."""

    path: str
    row_chunks: int
    rows_per_chunk: int
    tiles: tuple[tuple[int, int, int, int], ...]
    columns: int
    budget: int
    reach_gaps: int = 0
    conv_passes: int = 0
    conv_fill: float = 0.0

    def summary(self) -> dict:
        """What ``compile_cache.executables[].seg_plan`` shows."""
        return {
            "path": self.path,
            "row_chunks": self.row_chunks,
            "rows_per_chunk": self.rows_per_chunk,
            "column_tiles": len(self.tiles),
            "columns_per_tile_max": max((c for *_, c in self.tiles), default=0),
            "columns": self.columns,
            "budget_elements": self.budget,
            "reach_gaps": self.reach_gaps,
            "conv_passes": self.conv_passes,
            "conv_fill": round(self.conv_fill, 4),
        }


def _equal_chunks(t: int, rows_fit: int) -> tuple[int, int]:
    """(chunks, rows a chunk) for ``t`` rows at most ``rows_fit`` at a
    time. Chunks of equal size: 32 rows that fit 24 at a time are two
    chunks of 16, not two of 24 with a third of the conv on padding."""
    nc = -(-t // rows_fit)
    rows = -(-t // nc)
    if rows_fit >= 8:
        rows = -(-rows // 8) * 8  # up to a multiple of 8
    return nc, rows


def plan_segment_tier(
    specs,
    keep: tuple[int, ...],
    t: int,
    width: int,
    long_ok: bool,
    scan_past_one_chunk: bool = False,
) -> SegTierPlan:
    """The conv tier's plan for ``t`` rows of ``width`` bytes over the
    kept blocks' ``specs``, from shapes alone, under the device's budget
    (``_seg_chunk_elems``). The budget counts the
    DUPLICATED column count (``conv_n2_cols`` — what the [T, Q, N2] conv
    output actually allocates), not the deduped ``kernel.shape[2]``; the
    gapcls NCE tables are O(T·Q) a class plus constant O(B²) triangular
    tables at every width (``ops/segment.py:_excl_prefix_sum``) and need
    no budget term; nor do the reachability tables of a large structure's
    unbounded gaps (``_reach_tables``: 256 bytes a row position a class,
    what ONE pass of the latch they replace moved for 32 columns, under a
    structure whose own block is 24 MiB or more).

    Tiles: the most rows a chunk (all of them, then every multiple of
    eight downwards, then 4, 2, 1: fewest passes of the chains' many
    small operations) at which a tile of the budget still holds the
    widest group whole; ``long`` only where one row of the widest group
    does not fit (and ``long_ok``: long banks were built). So a feed of
    ten times the rules, or a tier twice as wide, is more tiles and not
    another path. ``scan_past_one_chunk`` (``_scan_past_one_chunk``: any
    backend but a TPU) sends a tier whose rows do not fit ONE chunk of
    tiles to the long scan instead of row chunks of tiles."""
    from ..ops.segment import (
        conv_fill,
        conv_n2_cols,
        conv_passes,
        cut_column_tiles,
        reach_gap_count,
        tile_spec,
        widest_group_cols,
    )

    budget = _seg_chunk_elems()

    def planned(path: str, nc: int, rows: int, tiles: tuple) -> SegTierPlan:
        reach = sum(reach_gap_count(tile_spec(specs[i], g0, g1), rows, q) for i, g0, g1, _ in tiles)
        # A tile's conv has its block's taps and channels: ``tile_spec`` cuts columns.
        passes = sum(conv_passes(specs[i]) for i, *_ in tiles)
        fill = sum(c * conv_fill(specs[i]) for i, _g0, _g1, c in tiles) / max(1, sum(c for *_, c in tiles))
        return SegTierPlan(path, nc, rows, tiles, columns, budget, reach, passes, fill)

    q = width + 2
    cols = {i: conv_n2_cols(specs[i]) for i in keep}
    columns = sum(cols.values())
    whole = tuple((i, 0, specs[i].n_groups, cols[i]) for i in keep)
    per_row = q * max(1, columns)
    if t * per_row <= budget or not keep:
        return planned("direct", 1, t, whole)
    rows_fit = budget // per_row // 8 * 8
    if rows_fit >= 8:
        return planned("rows", *_equal_chunks(t, rows_fit), whole)
    widest = max(widest_group_cols(specs[i]) for i in keep)
    t8 = -(-t // 8) * 8
    one_chunk_only = long_ok and scan_past_one_chunk
    for rows_fit in [t8] if one_chunk_only else [*range(t8, 0, -8), 4, 2, 1]:
        nc, rows = _equal_chunks(t, rows_fit)
        max_cols = budget // (rows * q)
        if max_cols < widest:
            continue
        # A block that fits is a tile; a wider one is dealt group by group.
        tiles: list[tuple[int, int, int, int]] = []
        for i in keep:
            if cols[i] <= max_cols:
                tiles.append((i, 0, specs[i].n_groups, cols[i]))
            else:
                tiles += [(i, *tile) for tile in cut_column_tiles(specs[i], max_cols)]
        return planned("tiles", nc, rows, tuple(tiles))
    if long_ok:
        return planned("long", 1, t, ())
    # Fallback disabled (or no long banks): direct conv regardless.
    return planned("direct", 1, t, whole)


def _block_on(mask: int | None, i: int) -> bool:
    """Whether the kind-partition ``mask`` scans block ``i`` (``match_tier``)."""
    return mask is None or i >= 62 or (mask >> i) & 1 == 1


def _scan_past_one_chunk() -> bool:
    """Whether a tier whose rows do not fit one chunk of column tiles
    takes the long scan (True) or row chunks of tiles (False). The two
    cost differently by platform, as ``ops/``'s kernels do: on a v5e
    row chunks of tiles are 5-8 times the scan's speed (crs-lite
    ``256x8192``: 0.66 s against 3.20 s a call; ``64x32768``: 0.60
    against 4.85; PR 43's chip run), because the scan's 8,192 or 32,768
    serial steps wait on each other while the conv fills the MXU; on
    XLA:CPU the scan is cheap and the conv's arithmetic is paid in full
    (tier-1's ``256x8192`` window of crs-lite: 67 s on the scan, 496 s
    in row chunks of tiles, and a crashed child with tiles of 124
    columns)."""
    return jax.default_backend() != "tpu"


def tier_seg_plan(
    model: WafModel, rows: int, width: int, mask: int | None = None
) -> SegTierPlan | None:
    """The plan ``match_tier`` traces its conv tier with at ``rows`` x
    ``width`` under ``mask``: the same function of the same statics, so
    the engine can say which plan an executable holds without tracing it
    (``compile_cache.executables[].seg_plan``). None for a model without
    segment blocks."""
    if not model.segs:
        return None
    return plan_segment_tier(
        [sb.spec for sb in model.segs],
        tuple(i for i in range(len(model.segs)) if _block_on(mask, i)),
        rows,
        width,
        long_ok=bool(model.long_banks) and _SEG_BITMAP_ELEMS > 0,
        scan_past_one_chunk=_scan_past_one_chunk(),
    )


def segment_tier_hits(
    segs,
    seg_pipelines,
    long_banks,
    long_bank_pipelines,
    seg_perm,
    data: jnp.ndarray,
    transformed_for,
    keep: tuple[int, ...] | None = None,
) -> list:
    """Hit blocks for the segment-routed groups, choosing the tier per
    TRACE (shapes are static per bucket, ``plan_segment_tier``): the conv
    tier materializes ~[T, L+2, N2] match-bitmap elements — linear in
    buffer length — so beyond the per-chunk budget the rows are processed
    in ``lax.map`` row chunks (same MXU convs, bounded peak HBM); where
    eight rows of all kept columns do not fit, the columns are cut into
    tiles along group boundaries, run one after another, over all the
    rows while they fit one chunk and inside row chunks past that; only
    when a SINGLE row of a single tile exceeds the budget (or, off the
    TPU, when the rows do not fit one chunk of tiles) does the bucket
    stream through the constant-memory DFA scan carry instead (same
    groups, same column order after ``seg_perm``). Shared by the
    single-chip ``eval_waf`` and the rule-sharded path
    (``parallel/mesh.py``).

    ``keep`` (kind-partitioned matching) lists the seg-block indexes the
    caller's rows can actually reach; skipped blocks contribute all-False
    hit columns (exact: post_match's ``rel`` gate resolves their links
    False for such rows). The long-bank fallback ignores ``keep`` — it
    is the rare giant-buffer path and scans everything."""
    from ..ops.segment import tile_spec

    if not segs:
        return []
    if keep is None:
        keep = tuple(range(len(segs)))
    t = data.shape[0]

    def zeros_for(i):
        return jnp.zeros((t, segs[i].n_groups), dtype=bool)

    plan = plan_segment_tier(
        [sb.spec for sb in segs],
        keep,
        t,
        data.shape[1],
        long_ok=bool(long_banks) and _SEG_BITMAP_ELEMS > 0,
        scan_past_one_chunk=_scan_past_one_chunk(),
    )
    if plan.path == "direct":
        return [
            match_segment_block(
                segs[i].kernel,
                segs[i].spec,
                *transformed_for(seg_pipelines[i]),
                block_index=i,
            )
            if i in keep
            else zeros_for(i)
            for i in range(len(segs))
        ]
    if plan.path == "long":
        long_cols = []
        for bank, pid in zip(long_banks, long_bank_pipelines):
            td = transformed_for(pid)
            with jax.named_scope("cko.seg.long"):
                long_cols.append(scan_dfa_bank(bank, *td))
        with jax.named_scope("cko.seg.long"):
            lh = jnp.concatenate(long_cols, axis=1)  # [T, Gs] in long order
            return [
                jnp.dot(
                    lh.astype(jnp.bfloat16),
                    seg_perm.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
                > 0
            ]  # [T, Gs] in seg-column order
    # Row-chunked conv tier: pad rows to a chunk multiple, stack the
    # per-pipeline transformed buffers, and run every kept segment
    # block on one chunk per lax.map step. Padding rows are all-NUL
    # with length 0 — their hits are computed but never read (uid
    # indexes only real unique rows).
    pids = sorted({seg_pipelines[i] for i in keep})
    pid_ix = {pid: i for i, pid in enumerate(pids)}
    nc, rows_fit = plan.row_chunks, plan.rows_per_chunk
    tp = nc * rows_fit
    stacked_d, stacked_l = [], []
    for pid in pids:
        td, tl = transformed_for(pid)
        with jax.named_scope("cko.seg.chunk"):
            stacked_d.append(
                jnp.pad(td, ((0, tp - t), (0, 0))).reshape(
                    nc, rows_fit, td.shape[1]
                )
            )
            stacked_l.append(jnp.pad(tl, (0, tp - t)).reshape(nc, rows_fit))
    tiled = plan.path == "tiles"

    def one_chunk(args):
        """The chunk's tiles (whole blocks unless ``tiled``), their hits
        side by side: a block's tiles are consecutive and in group order.
        Column tiles run one after another: a tile's rows pass an
        optimization barrier with the hits of the tile before, so no two
        tiles' bitmaps are alive at once."""
        out = []
        for i, g0, g1, _cols in plan.tiles:
            ds, ls = args
            hits = match_segment_block(
                segs[i].kernel,
                tile_spec(segs[i].spec, g0, g1),
                ds[pid_ix[seg_pipelines[i]]],
                ls[pid_ix[seg_pipelines[i]]],
                block_index=i,
            )
            if tiled:
                with jax.named_scope("cko.seg.tile"):
                    args, hits = jax.lax.optimization_barrier((args, hits))
            out.append(hits)
        with jax.named_scope("cko.seg.tile") if tiled else contextlib.nullcontext():
            return jnp.concatenate(out, axis=1)

    # The blocks inside the map keep their own scopes: an operation
    # stands under the innermost scope of its name.
    with jax.named_scope("cko.seg.chunk"):
        stacked = (jnp.stack(stacked_d, axis=1), jnp.stack(stacked_l, axis=1))
        if nc > 1:
            hits = jax.lax.map(one_chunk, stacked)
        else:  # every row in one chunk of tiles: no loop around them
            hits = one_chunk((stacked[0][0], stacked[1][0]))[None]
        hits = hits.reshape(tp, hits.shape[2])[:t]
        # Reassemble full column order, zero blocks for skipped segs.
        out, off = [], 0
        for i in range(len(segs)):
            if i in keep:
                g = segs[i].n_groups
                out.append(hits[:, off : off + g])
                off += g
            else:
                out.append(zeros_for(i))
    return out


def _compare(cmp: jnp.ndarray, left: jnp.ndarray, right: jnp.ndarray) -> jnp.ndarray:
    """Vectorized six-way comparison (codes from operators.CMP_CODES)."""
    return jnp.select(
        [cmp == 0, cmp == 1, cmp == 2, cmp == 3, cmp == 4, cmp == 5],
        [left == right, left != right, left >= right, left > right, left <= right, left < right],
        default=False,
    )


@partial(jax.jit, static_argnames=("max_phase",))
def eval_waf(
    model: WafModel,
    data: jnp.ndarray,  # [T, L] uint8 base target buffer
    lengths: jnp.ndarray,  # [T]
    kind1: jnp.ndarray,  # [T] target kind ids (0 = none)
    kind2: jnp.ndarray,
    kind3: jnp.ndarray,
    req_id: jnp.ndarray,  # [T] owning request (B = padding bucket)
    numvals: jnp.ndarray,  # [B, NV] int32
    variant_data: jnp.ndarray,  # [H, T, L] host-pipeline variants
    variant_lengths: jnp.ndarray,  # [H, T]
    max_phase: int = 2,
):
    """Evaluate one batch. Returns a dict of per-request verdict arrays."""
    group_hits = match_tier(model, data, lengths, variant_data, variant_lengths)
    return post_match(
        model, group_hits, kind1, kind2, kind3, req_id, numvals, max_phase
    )


def match_tier(
    model: WafModel,
    data: jnp.ndarray,  # [T, L] uint8
    lengths: jnp.ndarray,  # [T]
    variant_data: jnp.ndarray,  # [H, T, L]
    variant_lengths: jnp.ndarray,  # [H, T]
    mask: int | None = None,
) -> jnp.ndarray:
    """Stages 1+2 for ONE length tier: transforms + matchers → per-target
    group hits [T, G]. Segment blocks first, dense-DFA blocks after — the same
    global order build_model's remap assigned. Tiers are independent
    until post_match (rows only meet at the req_id reduction), which is
    what makes row-level length tiering (``engine.waf.tier_tensors``)
    sound: each tier's matcher runs at its own buffer width (conv work
    is linear in Q = L + 2), so a long request's short rows never pay
    the body's width.

    ``mask`` (static int) is the kind-partition block bitmask: bit i set
    = scan block i (segs first, then the dense blocks — build_model order). Bits
    0-61 are usable; blocks at index >= 62 are always scanned
    (saturation for huge models). A
    skipped block contributes all-False hits, which is exact for rows
    whose kinds cannot reach the block's groups (``rel`` in post_match
    gates those links off regardless of the hit bit)."""
    per_block: list[jnp.ndarray] = []
    transformed: dict[int, tuple[jnp.ndarray, jnp.ndarray]] = {}
    n_segs = len(model.segs)

    def block_on(i: int) -> bool:
        return _block_on(mask, i)

    def transformed_for(pid: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        if pid not in transformed:
            slot = model.host_variant_index[pid]
            if slot >= 0:
                transformed[pid] = (variant_data[slot], variant_lengths[slot])
            else:
                names = model.pipelines[pid]
                with jax.named_scope("cko.transform"), jax.named_scope(
                    "+".join(names) or "none"
                ):
                    transformed[pid] = apply_device_pipeline(data, lengths, names)
        return transformed[pid]

    per_block.extend(
        segment_tier_hits(
            model.segs,
            model.seg_pipelines,
            model.long_banks,
            model.long_bank_pipelines,
            model.seg_perm,
            data,
            transformed_for,
            keep=tuple(i for i in range(n_segs) if block_on(i)),
        )
    )
    # Flat-slot fused bins: one fused scan covers many blocks. A bin runs
    # when ANY of its blocks is mask-on; mask-off blocks' columns are
    # discarded (the stitcher emits zeros for them below, which is exact
    # — post_match's rel gate resolves those links False regardless).
    flat_cols: dict[int, dict[int, jnp.ndarray]] = {}
    for fi, fb in enumerate(model.flat_banks):
        if not any(block_on(p[0]) for p in fb.pieces):
            continue
        sub = {p: transformed_for(p) for p in sorted(set(fb.seg_pipes))}
        with jax.named_scope("cko.flat"):
            out = scan_flat_bank(fb, sub, name=f"cko_flat_bin{fi}")
            col = 0
            for blk, g_lo, g_hi in fb.pieces:
                w = g_hi - g_lo
                flat_cols.setdefault(blk, {})[g_lo] = out[:, col : col + w]
                col += w
    # The dense-DFA blocks in the global column order. A flat-covered
    # block takes its columns from its bins; the plain bank scan is for a
    # block the flat planner left out.
    bank_of = dict(zip(model.bank_blocks, model.banks))
    for blk, db in enumerate(model.dense_blocks, start=n_segs):
        if not block_on(blk):
            with jax.named_scope("cko.stitch"):
                per_block.append(jnp.zeros((data.shape[0], db.groups), dtype=bool))
        elif blk in bank_of:
            td = transformed_for(db.pipeline)
            with jax.named_scope("cko.dense"):
                per_block.append(scan_dfa_bank(bank_of[blk], *td))
        else:
            pieces = flat_cols[blk]
            with jax.named_scope("cko.stitch"):
                per_block.append(
                    jnp.concatenate([pieces[k] for k in sorted(pieces)], axis=1)
                )
    with jax.named_scope("cko.stitch"):
        if per_block:
            return jnp.concatenate(per_block, axis=1)  # [T, G]
        return jnp.zeros((data.shape[0], 1), dtype=bool)


def _unpack_hit_rows(packed: jnp.ndarray, g: int) -> jnp.ndarray:
    """[U, PB] uint8 (big bit order, np.packbits layout) -> [U, G] bool."""
    u, pb = packed.shape
    shifts = 7 - jnp.arange(8, dtype=jnp.uint8)
    bits = (packed[:, :, None] >> shifts[None, None, :]) & 1
    return bits.reshape(u, pb * 8)[:, :g].astype(bool)


def post_match(
    model: WafModel,
    group_hits: jnp.ndarray,  # [T, G]
    kind1: jnp.ndarray,
    kind2: jnp.ndarray,
    kind3: jnp.ndarray,
    req_id: jnp.ndarray,
    numvals: jnp.ndarray,
    max_phase: int = 2,
):
    """Stages 3-5: incidence, reductions, counters, verdict. Shared by the
    single-chip path and the sharded path (``parallel/mesh.py``), which
    arrives here after all-gathering rule-sharded group hits."""
    b = numvals.shape[0]
    k = model.inc.shape[0]

    # 3: incidence + per-target link matches. All the T-sized lookups are
    # one-hot matmuls: XLA's gather lowering serializes on TPU while these
    # contractions ride the MXU (measured ~100x on the same shapes). The
    # one-hot operands are cast to bf16 (0/1 and tiny counts — exact):
    # XLA lowers int8 DotGeneral off the MXU on TPU, bf16 is the native
    # systolic dtype.
    gm = (
        jnp.dot(
            group_hits.astype(jnp.bfloat16),
            model.e_lg.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        > 0
    )  # [T, Rl] == group_hits[:, lgroup]
    kinds_iota = jnp.arange(k, dtype=jnp.int32)[None, :]
    k_multi = (
        (kind1[:, None] == kinds_iota)
        | (kind2[:, None] == kinds_iota)
        | (kind3[:, None] == kinds_iota)
    ).astype(jnp.bfloat16)  # [T, K]
    rel = (
        jnp.dot(
            k_multi,
            model.inc.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        > 0
    )
    excl = (
        jnp.dot(
            k_multi,
            model.exc.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        > 0
    )
    str_t = rel & ~excl & (gm ^ model.lneg[None, :])  # [T, Rl]

    # 4a: targets → requests. One-hot matmul instead of scatter: scatters
    # serialize on TPU while this contraction rides the MXU (it also avoids
    # an XLA:CPU miscompile where scatter-max over a fused gather operand
    # read zeros). Padding rows carry req_id == B and select no column.
    # bf16 is exact: the contraction sums at most a few one-hot products
    # per output (#targets per request << 256).
    onehot = (req_id[:, None] == jnp.arange(b, dtype=req_id.dtype)[None, :])  # [T, B]
    m_str = (
        jnp.einsum(
            "tb,tr->br",
            onehot.astype(jnp.bfloat16),
            str_t.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        > 0
    )  # [B, Rl]

    # 4b: numeric links. One-hot f32 matmul, not numvals[:, lnumvar]: the
    # [B, Rl] dynamic gather serializes on TPU (profiled at a large share
    # of post_match). A single f32 contraction would round values >= 2^24
    # (REQUEST_BODY_LENGTH / FULL_REQUEST_LENGTH are attacker-controlled
    # and can exceed 16 MB, flipping size-limit rules), so the int32 is
    # split into 12-bit-shifted halves — each exact in f32 — and
    # recombined after the selection.
    def _sel_exact(values_i32: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
        hi = jnp.dot(
            (values_i32 >> 12).astype(jnp.float32),
            onehot,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        lo = jnp.dot(
            (values_i32 & 0xFFF).astype(jnp.float32),
            onehot,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        return (hi << 12) | lo

    vals = _sel_exact(numvals, model.e_numvar)  # [B, Rl]
    m_num = _compare(model.lcmp[None, :], vals, model.lcmparg[None, :]) ^ model.lneg[None, :]

    m_always = jnp.broadcast_to(~model.lneg[None, :], m_str.shape)
    m_never = jnp.broadcast_to(model.lneg[None, :], m_str.shape)

    lt = model.ltype[None, :]
    link_m = jnp.select(
        [lt == LINK_STRING, lt == LINK_NUMERIC, lt == LINK_ALWAYS, lt == LINK_NEVER],
        [m_str, m_num, m_always, m_never],
        default=False,
    )  # counter links False in the prelim pass

    def rules_from_links(lm: jnp.ndarray) -> jnp.ndarray:
        # AND over a rule's links == "every selected link matched", computed
        # as a multiplicity-count matmul (MXU) instead of a [B, Rr, MX]
        # gather: count of matched links must equal the rule's link count.
        # bf16 exact: counts <= MX (a rule's link count) << 256.
        counts = jnp.dot(
            lm.astype(jnp.bfloat16),
            model.m_count.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)  # [B, Rr]
        return counts == model.link_count[None, :]

    prelim = rules_from_links(link_m)

    # ctl:ruleRemoveById/ByTag — in-order semantics (ADVICE r3): walk the
    # remover rules in evaluation order; a ctl rule removed by an earlier
    # ctl never fires, so its own removals never apply (the build-time
    # matrix already restricts each row to LATER rules). The remover set
    # is small (CRS exception idiom: a handful of 9xx rules), so the
    # unrolled [B, Rr] masks cost far less than the matchers.
    removed = None
    if model.has_removals:
        removed = jnp.zeros_like(prelim)
        rem = model.removal != 0
        for c in model.removal_rows:
            fires = prelim[:, c] & ~removed[:, c]
            removed = removed | (fires[:, None] & rem[c][None, :])
        prelim = prelim & ~removed

    # 4c: anomaly-score counters + threshold links. f32 matmul (exact for
    # |weights| < 2^24) — an int32 matmul would not ride the MXU. Precision
    # HIGHEST keeps the operands f32 on TPU: the default precision demotes
    # to bf16 (8 mantissa bits), which silently corrupts any setvar
    # increment not bf16-representable.
    counters = model.counter_base[None, :] + jnp.dot(
        prelim.astype(jnp.float32),
        model.weights.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(jnp.int32)
    # counters[:, lcounter] as the same exact split contraction (see 4b).
    cvals = _sel_exact(counters, model.e_counter)  # [B, Rl]
    m_counter = _compare(model.lcmp[None, :], cvals, model.lcmparg[None, :]) ^ model.lneg[None, :]
    link_m = jnp.where(lt == LINK_COUNTER, m_counter, link_m)
    matched = rules_from_links(link_m)
    if removed is not None:
        matched = matched & ~removed

    if model.two_pass_counters:
        # Second counter pass: rules gated on a counter link are absent
        # from prelim (counter links resolve False there), so their own
        # setvar weights are missing from `counters`. Add the weights of
        # rules that matched only via counter links, then re-resolve the
        # counter links and the match set — exact for the CRS shape
        # (ctl-variant rules score; 949110-style threshold rules don't).
        extra = matched & ~prelim
        counters = counters + jnp.dot(
            extra.astype(jnp.float32),
            model.weights.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        cvals = _sel_exact(counters, model.e_counter)
        m_counter = (
            _compare(model.lcmp[None, :], cvals, model.lcmparg[None, :])
            ^ model.lneg[None, :]
        )
        link_m = jnp.where(lt == LINK_COUNTER, m_counter, link_m)
        matched = rules_from_links(link_m)
        if removed is not None:
            matched = matched & ~removed

    # 5: verdict — first matched decision rule in phase order.
    in_scope = (model.decision[None, :] != 0) & (model.phase[None, :] <= max_phase)
    keys = jnp.where(matched & in_scope, model.order_key[None, :], _BIG)
    first_key = keys.min(axis=1)
    first_idx = keys.argmin(axis=1)
    has_decision = first_key < _BIG
    dec = model.decision[first_idx]
    interrupts = (dec == DEC_DENY) | (dec == DEC_DROP) | (dec == DEC_REDIRECT)
    engine_active = model.engine_on and not model.detection_only
    interrupted = has_decision & interrupts & engine_active
    status = jnp.where(interrupted, model.status[first_idx], 200)
    rule_index = jnp.where(has_decision, first_idx, -1)

    return {
        "matched": matched,  # [B, Rr]
        "interrupted": interrupted,  # [B]
        "status": status,  # [B]
        "rule_index": rule_index,  # [B]
        "scores": counters,  # [B, C]
    }


def _pack_verdicts(out) -> jnp.ndarray:
    """Pack eval's verdict dict into ONE int32 array [B, 3 + nw + C]:
    columns 0-2 are (interrupted, status, rule_index), then bit-packed
    matched words, then the counters. Serving reads ~25x fewer bytes in
    ONE transfer — device->host readback (per-transfer round trips +
    bandwidth) is the serving bottleneck once the host path is native.
    Unpack with ``unpack_compact``."""
    b = out["status"].shape[0]
    head = jnp.stack(
        [
            out["interrupted"].astype(jnp.int32),
            out["status"].astype(jnp.int32),
            out["rule_index"].astype(jnp.int32),
        ],
        axis=1,
    )  # [B, 3]
    bits = jnp.packbits(out["matched"].astype(jnp.uint8), axis=1)
    nb = bits.shape[1]
    pad = (-nb) % 4
    bits = jnp.pad(bits, ((0, 0), (0, pad)))
    words = jax.lax.bitcast_convert_type(
        bits.reshape(b, (nb + pad) // 4, 4), jnp.int32
    )  # [B, nw]
    return jnp.concatenate([head, words, out["scores"]], axis=1)


# -- the served entry point: one matcher executable per tier, one post stage ---
#
# A window is evaluated by ``match_tier_packed`` once per length tier and
# one ``eval_post_tiered`` over all of them (WafEngine._dispatch_tiers).
# The stages compile independently: same-shape tiers across windows and
# tenants share one matcher executable, a thread pool compiles them in
# parallel (XLA releases the GIL), a tier-shape change recompiles that
# tier alone, and a tier whose executable has not landed yet can route
# through the host fallback meanwhile (engine/tier_compile.py). Between
# the stages the group hits travel bit-packed: packbits/unpackbits over
# G bits is lossless, so the pair computes exactly ``eval_waf``'s math
# (``match_tier`` then ``post_match``), tier by tier.


@partial(jax.jit, static_argnames=("mask",))
def match_tier_packed(
    model: WafModel,
    slab: jnp.ndarray,  # uint8 [1 + H + E, U, L]: the tier's match slab
    mask: int | None = None,
) -> jnp.ndarray:
    """One tier's matcher stage, the executable a window launches per
    tier: transforms + matchers (``match_tier``) over the tier's UNIQUE
    target values (a serving window repeats header values, names and
    hot paths, so the rows collapse before they reach a matcher),
    bit-packed to [U, PB] uint8 (np.packbits layout — the same format
    the value cache stores and ``eval_post_tiered`` / the host post path
    unpack). The tier arrives as ONE host operand, its match slab
    (``models/slab.py``): ``data [U, L]``, ``vdata [H, U, L]`` and the
    ``int32`` ``lengths [U]`` / ``vlengths [H, U]`` are static slices of
    it."""
    with jax.named_scope("cko.slab"):
        data, lengths, variant_data, variant_lengths = unpack_match_slab(slab)
    hits_u = match_tier(model, data, lengths, variant_data, variant_lengths, mask=mask)
    with jax.named_scope("cko.stitch"):
        return jnp.packbits(hits_u.astype(jnp.uint8), axis=1)


@partial(jax.jit, static_argnames=("max_phase", "layout"))
def eval_post_tiered(
    model: WafModel,
    tier_hits,  # tuple of [U, PB] uint8 per tier (packed matcher rows)
    slab: jnp.ndarray,  # int32 [words]: the window's post slab
    max_phase: int = 2,
    layout: tuple = (),  # slab.post_layout: (((P, Uc), ...), B, NV, PB)
) -> jnp.ndarray:
    """The post stage, one executable a window: unpack each tier's
    packed hit rows (matcher output or host-computed — same shapes, same
    bit layout, so provenance never changes the trace), append the
    tier's cross-batch cached rows (``engine.value_cache``: ``uid`` then
    indexes [matcher rows | cached rows], so a cached value never
    touches a matcher), expand to per-(target, kinds) pair rows via
    ``uid``, and run ONE global ``post_match`` + ``_pack_verdicts``.
    Request atomicity holds because req_id is global across tiers and
    post_match is the only cross-row stage. Everything but the hit rows
    arrives as ONE operand, the window's post slab (``models/slab.py``;
    ``layout`` says where each field lies): per tier ``(kind1, kind2,
    kind3, req_id, uid)``, ``numvals``, and per tier the cached rows
    ``[Uc, PB] uint8`` where the value cache is on. Nothing in it waits
    for a matcher, so the engine puts it on the device before it waits
    for one."""
    with jax.named_scope("cko.post.unpack"):
        pairs, numvals, cached = unpack_post_slab(slab, layout)
        g = model.e_lg.shape[0]
        hits, k1s, k2s, k3s, rids = [], [], [], [], []
        for ti, (hp, (k1, k2, k3, rid, uid)) in enumerate(zip(tier_hits, pairs)):
            hu = _unpack_hit_rows(hp, g)
            if cached is not None and cached[ti] is not None:
                hu = jnp.concatenate([hu, _unpack_hit_rows(cached[ti], g)], axis=0)
            hits.append(jnp.take(hu, uid, axis=0))  # [P, G] pair rows
            k1s.append(k1)
            k2s.append(k2)
            k3s.append(k3)
            rids.append(rid)
        operands = (
            jnp.concatenate(hits, axis=0),
            jnp.concatenate(k1s),
            jnp.concatenate(k2s),
            jnp.concatenate(k3s),
            jnp.concatenate(rids),
        )
    with jax.named_scope("cko.post.match"):
        out = post_match(model, *operands, numvals, max_phase)
    with jax.named_scope("cko.post.pack"):
        return _pack_verdicts(out)


def unpack_compact(packed: np.ndarray, n_rules: int, n_counters: int):
    """Host-side split of ``_pack_verdicts``' packed array (numpy)."""
    nb = (n_rules + 7) // 8
    nw = (nb + 3) // 4
    head = packed[:, :3]
    words = np.ascontiguousarray(packed[:, 3 : 3 + nw])
    bits = words.view(np.uint8).reshape(packed.shape[0], nw * 4)[:, :nb]
    matched = np.unpackbits(bits, axis=1, count=n_rules).astype(bool)
    scores = packed[:, 3 + nw : 3 + nw + n_counters]
    return head, matched, scores


def matched_id_lists(
    matched: np.ndarray,
    rule_ids: np.ndarray,
    n_real_rules: int,
    n_requests: int,
) -> list[list[int]]:
    """Per-request matched-rule-id lists from the unpacked matched
    matrix, in ONE vectorized pass: a single ``np.nonzero`` over the
    real-rule columns plus a boundary split, instead of a per-row
    ``np.flatnonzero`` loop (the decode stage of the pipelined collect
    path is host-serial, so it must stay O(total hits), not
    O(batch x rules)). Column order is preserved, so the lists are
    bit-identical to the per-row loop's output."""
    m = matched[:n_requests, :n_real_rules]  # drop the >=1-row pad rule
    req_idx, rule_idx = np.nonzero(m)
    if req_idx.size == 0:
        return [[] for _ in range(n_requests)]
    ids = rule_ids[rule_idx]
    splits = np.searchsorted(req_idx, np.arange(1, n_requests))
    return [a.tolist() for a in np.split(ids, splits)]


# The split stages under module names a trace reduction can find after
# the rule set changes: role and window shape, e.g.
# ``jit_cko_match_32x512`` / ``jit_cko_eval_post_32x512`` (one shape per
# tier, joined by ``_``), where jit would name every shape
# ``jit_match_tier_packed``. ``eval_post`` names the post stage alone
# (wafbench/layer_metrics/matcher_device_ms_per_window.py finds it by
# that). The name is in the persistent compile cache's key.
_STAGE_FNS = {
    "match": (match_tier_packed, ("mask",)),
    "eval_post": (eval_post_tiered, ("max_phase", "layout")),
}
_stage_executables: dict[tuple[str, str], object] = {}


def stage_executable(role: str, shape: str):
    """``match_tier_packed`` / ``eval_post_tiered`` jitted under the
    module name ``cko_<role>_<shape>``; one object per name, so the
    executable cache keys on it."""
    fn = _stage_executables.get((role, shape))
    if fn is None:
        jitted, static = _STAGE_FNS[role]
        inner = jitted.__wrapped__

        def stage(*args, **kwargs):
            return inner(*args, **kwargs)

        stage.__name__ = stage.__qualname__ = f"cko_{role}_{shape}"
        # setdefault: two lanes may race the first window of a shape.
        fn = _stage_executables.setdefault(
            (role, shape), jax.jit(stage, static_argnames=static)
        )
    return fn
