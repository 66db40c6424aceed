"""Flat-slot fused multi-bank DFA scan — bank fusion for the matcher tier.

Round-4 profiling attributed ~96% of the CRS-scale device step to 19
matcher stages whose cost is per-stage fixed work, not FLOPs:
every DFA bank was its own scan, small banks padded their group axis to
128 lanes, banks with S > 128 states fell to XLA's serializing gather,
and the hot S=104 x G=84 bank exceeded the per-bank Pallas VMEM budget
and ran the HBM take-scan (one [B, S*G] HBM intermediate per byte).

This module fuses MANY heterogeneous-S banks into ONE scan by
flattening every (group, local state) pair into one slot axis:

- slot n holds group ``g(n)``'s local state ``n - base_g``;
- the machine state is a one-hot over slots (``sigma`` [B, N]);
- one byte step is four MXU matmuls + VPU elementwise:
    r      = onehot(byte) @ table       # [B, N] packed next + S*emit
    val    = (sigma * r) @ sel          # [N, G] 0/1 -> per-group value
    hit    = val >= S_g ; nxt = val - S_g*hit
    lo, hi = digits(base_g + nxt)       # the target slot, base 256
    sigma' = (lo @ bcast == slot_lo) & (hi @ bcast == slot_hi)
                                        # [G, N] 0/1 spread, re-one-hot
  (a row past its length keeps its sigma);
- no per-bank lane padding: a 7-group bank costs its ~400 slots, not
  7 x 128 padded columns.

Banks are greedily binned under the Pallas VMEM budget (big-G banks are
split by group ranges — groups are independent, so any split is sound);
each bin runs as ONE Pallas kernel on TPU (``_flat_kernel``) or one XLA
``lax.scan`` with identical math elsewhere (``scan_flat_xla``).

Numerics: every number a matmul carries is a whole number below 256
in a bf16 operand, so ONE bf16 MXU pass with f32 accumulation is exact
and no dot leans on a backend's f32 matmul precision. (At its default a
TPU runs an f32 dot as one bf16 pass, in XLA and in Mosaic alike: the
f32 formulation this replaces lost the low bits of every slot index
above 256 on the chip, so a group laid past slot 256 fell back to a
neighbouring or the start state — PR 31 found it when the dfa-hot and
prefilter groups moved into bins of 640 and 1,664 slots; the CPU and
the interpreter compute f32 dots exactly and never showed it.) Table
values ``next + S*emit`` < 2*S are stored as base-256 digit planes (one
plane when 2*S <= 256, two above); slot targets (up to N) are split
into the same two digits before they are spread over the slots. The
one-hot/select operands are 0/1. Everything between the dots is f32
elementwise arithmetic on whole numbers below 2^24.

Padding: each table segment's slot count and the group axis are padded
to lane multiples (128). Dead slots carry all-zero table columns, zero
``sel``/``bcast``/``init_sigma`` — their sigma can never become 1
(both spread digits read 0 there while the slot's own do not, but for
slot 0, which is always real).
Dead groups carry ``S_g`` = 2^30 (hit impossible) and zero map columns.

Reference parity: same matcher contract as ``ops/dfa.py:scan_dfa_bank``
(matched[b, g] == "group g's regex matched row b"), re-planned for the
TPU's preference for one big fused kernel over many small sequential
ones. Differential tests pin it to the gather oracle.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.re_dfa import DFA

_LANE = 128
# Per-kernel VMEM ceiling. The chip enforces a 16MB scoped-vmem limit at
# COMPILE time (observed: a 3584-slot bin over two pipelines at L=2048
# rejected at 16.09M/16.00M with a clean compile error — not the
# round-4 style runtime fault). The estimator below is calibrated
# against that measurement; the budget keeps ~1MB of margin under the
# real limit.
_FLAT_VMEM_BUDGET = 15 * 2**20
CHIP_SCOPED_VMEM_BYTES = 16 * 2**20  # what a v5e's compiler gives one kernel
MAX_BIN_SLOTS = 6144  # a bin's slot digits are base 256, two of them: far below 65536
_BLOCK_B = 128
_DEAD_S = float(2**30)  # pad-group state count: hit threshold never reached
_DIGIT = 256  # whole numbers below it are exact in bf16: the matmuls' digit base


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


@jax.tree_util.register_pytree_node_class
@dataclass
class FlatBank:
    """One fused scan bin: N slots over G groups, table segmented by
    (pipeline, one- or two-digit) runs along the slot axis.

    OPERAND DISCIPLINE (shape-canonical executable reuse,
    ``engine/compile_cache.py``): tables/maps are pytree LEAVES (runtime
    operands); only slot-layout statics (seg_pipes/seg_slots/group_pipe/
    pieces — they shape the traced program) live in the aux. Same-layout
    rulesets then share one compiled executable with their own tables
    swapped in at call time."""

    # per segment: the base-256 digit planes of its packed values, low
    # digit first — ([256, N_seg] bf16,) or two of them where a DFA of
    # the segment has 2*S > 256 (N_seg % 128 == 0)
    tables: tuple
    sel: jnp.ndarray  # [N, Gp] bf16 0/1: slot -> its group column
    bcast: jnp.ndarray  # [Gp, N] bf16 0/1: group -> its slots
    init_sigma: jnp.ndarray  # [1, N] f32: one-hot of each group's state 0
    mend: jnp.ndarray  # [1, N] f32: 1 when the slot's state is match_end
    base_g: jnp.ndarray  # [1, Gp] f32 slot base per group
    s_g: jnp.ndarray  # [1, Gp] f32 state count per group (hit threshold)
    always: jnp.ndarray  # [G] bool (unpadded)
    # static
    seg_pipes: tuple = ()  # pipeline id per table segment
    seg_slots: tuple = ()  # padded slot count per table segment
    group_pipe: tuple = ()  # pipeline id per (real) group
    pieces: tuple = ()  # (block_index, g_lo, g_hi) per covered group run

    def tree_flatten(self):
        leaves = (
            self.tables,
            self.sel,
            self.bcast,
            self.init_sigma,
            self.mend,
            self.base_g,
            self.s_g,
            self.always,
        )
        aux = (self.seg_pipes, self.seg_slots, self.group_pipe, self.pieces)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def n_slots(self) -> int:
        return int(self.sel.shape[0])

    @property
    def n_groups_padded(self) -> int:
        return int(self.sel.shape[1])

    @property
    def n_groups(self) -> int:
        return int(self.always.shape[0])


def flat_vmem_bytes(
    n_slots: int,
    n_groups: int,
    table_bytes: int,
    length: int,
    n_pipes: int = 2,
) -> int:
    """Resident-set estimate for one fused kernel, CALIBRATED against
    the chip's compile-time scope accounting: a 3584-slot 2-pipe bin at
    L=2048 measured 16.09MB = tables(1.8) + sel/bcast(1.8) + dataT
    tiles(4x s32[2048,128] = 4.0) + per-step work(~8.5 -> ~2370 B/slot
    ~= 128 x N x 18.5). The work coefficient uses 20 for margin."""
    n = _round_up(max(1, n_slots), _LANE)
    g = _round_up(max(1, n_groups), _LANE)
    consts = table_bytes + n * g * 2 * 2 + 4 * 4 * n + 4 * 4 * g
    work = _BLOCK_B * n * 20
    work_g = _BLOCK_B * g * 4 * 6
    data_tile = length * _BLOCK_B * 4 * 2 * max(1, n_pipes)
    return consts + work + work_g + data_tile


def _dfa_table_bytes(d: DFA) -> int:
    return 256 * _round_up(d.n_states, 1) * (2 if 2 * d.n_states <= 256 else 4)


def _layout_stats(pieces) -> tuple[int, int, int, int]:
    """(padded_slots, groups, table_bytes, n_pipes) exactly as
    ``build_flat_bank`` will lay this piece list out — every
    (pipeline, dtype-class) run pads to a lane multiple, so the planner
    budgets the REAL slot count, not the raw sum (review r5: the raw sum
    underestimated interleaved small-bank bins)."""
    total = 0
    run_slots = 0
    prev = None
    groups = 0
    tbytes = 0
    pipes = set()
    for _blk, pid, _lo, _hi, ds in pieces:
        pipes.add(pid)
        for d in ds:
            key = (pid, 2 * d.n_states <= 256)
            if prev is not None and key != prev and run_slots:
                total += _round_up(run_slots, _LANE)
                run_slots = 0
            prev = key
            run_slots += d.n_states
            groups += 1
            tbytes += _dfa_table_bytes(d)
    total += _round_up(run_slots, _LANE)
    return total, groups, tbytes, max(1, len(pipes))


# Widest buffer the Pallas kernel accepts; wider tiers run the XLA
# formulation (they carry few rows — the body tier is ~128 — so grid
# parallelism is nil there anyway). The planner sizes every bin for
# THIS width (``length_hint``: the data tiles are the one term of
# ``flat_vmem_bytes`` that grows with it), so a bin it makes fits at
# every width the engine launches it at, and a rule set of any size
# needs no knob: what does not fit one bin goes to the next. The
# ceiling is the chip's 16MB scoped-vmem limit, which the compiler
# enforces with a clean compile-time error, never a runtime fault.
# Measured (PR 37): crs-lite plus a 5,000-rule feed plans 18 bins, 16
# of them 3,456 slots x 128 groups on one pipeline (estimate 14.2MB of
# the 15MB budget at 2048), and all 18 compile for a v5e at widths 512
# and 2048 (chip calls and tests/test_tpu_compile.py); the one refusal
# on record, a 3,584-slot bin over two pipelines at 2048 (16.09M of
# 16.00M), reads 16.7MB on the estimator, so the planner never makes it
# (tests/test_custom_feed.py).
_PALLAS_MAX_LEN = 2048


def plan_flat_bins(
    bank_dfas: list[tuple[int, int, list[DFA]]],
    max_slots: int = MAX_BIN_SLOTS,
    budget: int = _FLAT_VMEM_BUDGET,
    length_hint: int = _PALLAS_MAX_LEN,
) -> tuple[list[list[tuple[int, int, int, int, list[DFA]]]], set[int]]:
    """Greedy bin-packing of (block_index, pipeline, dfas) banks into
    fused-kernel bins; oversized banks split by group ranges. Returns
    (bins, rejected_blocks): bins of (block_index, pid, g_lo, g_hi,
    dfas-slice) pieces, plus block indexes whose single-DFA working set
    exceeds the budget (``build_model`` stacks a ``DFABank`` for those
    and ``match_tier`` scans it with ``ops/dfa.py:scan_dfa_bank``).

    Packing is per pipeline, in block order: kind-partition masks tend
    to exclude whole pipelines, so a mask usually skips or keeps a whole
    bin, and stitching stays order-simple."""
    rejected: set[int] = set()
    for block_idx, _pid, dfas in bank_dfas:
        for d in dfas:
            if (
                flat_vmem_bytes(
                    _round_up(d.n_states, _LANE), 1, _dfa_table_bytes(d),
                    length_hint, 1,
                )
                > budget
            ):
                rejected.add(block_idx)
                break

    def fits(pieces: list) -> bool:
        slots, groups, tbytes, pipes = _layout_stats(pieces)
        return (
            slots <= max_slots
            and flat_vmem_bytes(slots, groups, tbytes, length_hint, pipes)
            <= budget
        )

    pieces: list[tuple[int, int, int, int, list[DFA]]] = []
    for block_idx, pid, dfas in bank_dfas:
        if block_idx in rejected:
            continue
        start = 0
        cur: list[DFA] = []
        for gi, d in enumerate(dfas):
            if cur and not fits([(block_idx, pid, start, gi, cur + [d])]):
                pieces.append((block_idx, pid, start, gi, cur))
                start, cur = gi, []
            cur.append(d)
        if cur:
            pieces.append((block_idx, pid, start, start + len(cur), cur))

    bins: list[list[tuple[int, int, int, int, list[DFA]]]] = []
    by_pid: dict[int, list] = {}
    for p in pieces:
        by_pid.setdefault(p[1], []).append(p)
    for pid in sorted(by_pid):
        cur_bin: list = []
        for p in by_pid[pid]:
            if cur_bin and not fits(cur_bin + [p]):
                bins.append(cur_bin)
                cur_bin = []
            cur_bin.append(p)
        if cur_bin:
            bins.append(cur_bin)

    # Second pass: merge small bins ACROSS pipelines (the kernel takes
    # one dataT per pipeline) while the union fits — every bin is a
    # sequential kernel launch, and a 128-slot singleton costs nearly as
    # much wall time as a 2048-slot bin. Greedy smallest-first.
    bins.sort(key=lambda bn: _layout_stats(bn)[0])
    merged: list[list] = []
    for bn in bins:
        placed = False
        for mb in merged:
            if fits(mb + bn):
                mb.extend(bn)
                placed = True
                break
        if not placed:
            merged.append(list(bn))
    return merged, rejected


def build_flat_bank(bin_pieces: list[tuple[int, int, int, int, list[DFA]]]) -> FlatBank:
    """Lay one bin out as device arrays (host-side numpy)."""
    entries: list[tuple[DFA, int]] = []  # (dfa, pid) in slot/group order
    pieces_static = []
    for block_idx, pid, g_lo, g_hi, ds in bin_pieces:
        pieces_static.append((block_idx, g_lo, g_hi))
        for d in ds:
            entries.append((d, pid))

    # Segment runs: consecutive entries sharing (pid, bf16-class).
    def klass(d: DFA) -> bool:
        return 2 * d.n_states <= 256

    runs: list[tuple[int, bool, list[DFA]]] = []
    for d, pid in entries:
        kc = klass(d)
        if runs and runs[-1][0] == pid and runs[-1][1] == kc:
            runs[-1][2].append(d)
        else:
            runs.append((pid, kc, [d]))

    g_total = len(entries)
    gp_total = _round_up(g_total, _LANE)
    n_total = sum(_round_up(sum(d.n_states for d in ds), _LANE) for _, _, ds in runs)

    sel = np.zeros((n_total, gp_total), dtype=np.float32)
    init_sigma = np.zeros((1, n_total), dtype=np.float32)
    mend = np.zeros((1, n_total), dtype=np.float32)
    base_g = np.zeros((1, gp_total), dtype=np.float32)
    s_g = np.full((1, gp_total), _DEAD_S, dtype=np.float32)
    always = np.zeros(g_total, dtype=bool)
    group_pipe: list[int] = []

    tables: list[jnp.ndarray] = []
    seg_pipes: list[int] = []
    seg_slots: list[int] = []
    off = 0
    gi = 0
    for pid, kc, ds in runs:
        seg_n_raw = sum(d.n_states for d in ds)
        seg_n = _round_up(seg_n_raw, _LANE)
        tab = np.zeros((256, seg_n), dtype=np.float32)
        seg_off = 0
        for d in ds:
            s = d.n_states
            tab[:, seg_off : seg_off + s] = (
                d.trans[:, d.classmap] + s * d.emit[:, d.classmap].astype(np.int64)
            ).T
            a = off + seg_off
            sel[a : a + s, gi] = 1.0
            init_sigma[0, a] = 1.0
            mend[0, a : a + s] = d.match_end.astype(np.float32)
            base_g[0, gi] = a
            s_g[0, gi] = s
            always[gi] = d.always_match
            group_pipe.append(pid)
            gi += 1
            seg_off += s
        planes = (tab % _DIGIT,) if kc else (tab % _DIGIT, tab // _DIGIT)
        assert float(planes[-1].max()) < _DIGIT, "a DFA of 32768 states or more"
        tables.append(tuple(jnp.asarray(pl).astype(jnp.bfloat16) for pl in planes))
        seg_pipes.append(pid)
        seg_slots.append(seg_n)
        off += seg_n

    return FlatBank(
        tables=tuple(tables),
        sel=jnp.asarray(sel).astype(jnp.bfloat16),
        bcast=jnp.asarray(sel.T).astype(jnp.bfloat16),
        init_sigma=jnp.asarray(init_sigma),
        mend=jnp.asarray(mend),
        base_g=jnp.asarray(base_g),
        s_g=jnp.asarray(s_g),
        always=jnp.asarray(always),
        seg_pipes=tuple(seg_pipes),
        seg_slots=tuple(seg_slots),
        group_pipe=tuple(group_pipe),
        pieces=tuple(pieces_static),
    )


def _digits(x):
    """Whole numbers below 65536 as their two base-256 digits, each a
    bf16 operand the MXU carries exactly: (low, high)."""
    hi = jnp.floor(x * (1.0 / _DIGIT))
    return (x - _DIGIT * hi).astype(jnp.bfloat16), hi.astype(jnp.bfloat16)


def _dot(a, b):
    """Every matmul of the scan. Its operands hold whole numbers below
    256 (bf16, or f32 0/1 for the activity masks), so the one bf16 pass
    a TPU gives a dot by default is exact (tests/test_dfa_flat.py runs
    the bins with the operands rounded to bf16 here)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _flat_step_math(
    sigma, matched, r_planes, active_g, active_n, sel, bcast, base_g, s_g, iota_lo, iota_hi
):
    """Shared per-byte math (Pallas kernel body and XLA fallback).

    sigma [B, N] f32 one-hot; matched [B, Gp] f32; r_planes: this byte's
    packed values as base-256 digit planes, [B, N] f32 each, low first;
    active_g [B, Gp] and active_n [B, N] f32 0/1: the row is inside its
    length, per group and per slot; sel [N, Gp] / bcast [Gp, N] bf16
    0/1; iota_lo / iota_hi [1, N] f32: the digits of each slot's index.
    Every dot takes bf16 operands that hold whole numbers below 256, at
    most one nonzero term per output: exact in one MXU pass."""
    val = 0.0
    for k, r in enumerate(r_planes):  # [B, Gp]: the group's value at its state
        val = val + float(_DIGIT**k) * _dot((sigma * r).astype(jnp.bfloat16), sel)
    hit = (val >= s_g).astype(jnp.float32)
    nxt = val - s_g * hit
    matched = jnp.maximum(matched, hit * active_g)
    t_lo, t_hi = _digits(base_g + nxt)  # absolute slot of the next state
    moved = (_dot(t_lo, bcast) == iota_lo) & (_dot(t_hi, bcast) == iota_hi)
    # A row past its length keeps its state (and so its end-anchor).
    sigma = jnp.where(active_n > 0, moved.astype(jnp.float32), sigma)
    return sigma, matched


def _slot_digits(slot):
    """[1, N] int32 slot indexes -> their base-256 digits as f32."""
    return (
        (slot % _DIGIT).astype(jnp.float32),
        (slot // _DIGIT).astype(jnp.float32),
    )


def _group_pipe_onehot(flat: FlatBank, pids: list[int]) -> np.ndarray:
    """[P, Gp] f32: group -> owning pipeline (pad groups all-zero)."""
    gp = np.zeros((len(pids), flat.n_groups_padded), dtype=np.float32)
    pid_ix = {p: i for i, p in enumerate(pids)}
    for gi, pid in enumerate(flat.group_pipe):
        gp[pid_ix[pid], gi] = 1.0
    return gp


def _by_segment(seg_slots, per_segment):
    """[B, N] from one [B, 1] column per table segment (a segment's
    slots all belong to its pipeline)."""
    b = per_segment[0].shape[0]
    return jnp.concatenate(
        [jnp.broadcast_to(col, (b, sn)) for col, sn in zip(per_segment, seg_slots)],
        axis=1,
    )


def _r_planes(seg_planes):
    """Per-segment digit planes ([B, N_seg] f32 each, low first) -> the
    bin's planes [B, N]; a segment without a high digit reads zero."""
    depth = max(len(planes) for planes in seg_planes)
    return [
        jnp.concatenate(
            [
                planes[k] if k < len(planes) else jnp.zeros_like(planes[0])
                for planes in seg_planes
            ],
            axis=1,
        )
        for k in range(depth)
    ]


def scan_flat_xla(
    flat: FlatBank, data_by_pipe: dict[int, tuple[jnp.ndarray, jnp.ndarray]]
) -> jnp.ndarray:
    """XLA lax.scan formulation — the CPU path and the semantic twin of
    the Pallas kernel (same ``_flat_step_math``)."""
    pids = sorted(set(flat.seg_pipes))
    d0 = data_by_pipe[pids[0]][0]
    b = d0.shape[0]
    n, gp_n = flat.n_slots, flat.n_groups_padded
    iota_lo, iota_hi = _slot_digits(jnp.arange(n, dtype=jnp.int32)[None, :])

    dataT = jnp.stack(
        [data_by_pipe[p][0].T for p in pids], axis=1
    ).astype(jnp.int32)  # [L, P, B]
    lens = jnp.stack([data_by_pipe[p][1] for p in pids], axis=0)  # [P, B]
    pid_ix = {p: i for i, p in enumerate(pids)}
    gp_j = jnp.asarray(_group_pipe_onehot(flat, pids))

    row0 = dataT[0, 0, :, None].astype(jnp.float32) * 0  # [B, 1] varying zero
    sigma0 = jnp.broadcast_to(flat.init_sigma, (b, n)).astype(jnp.float32) + row0
    matched0 = jnp.zeros((b, gp_n), dtype=jnp.float32) + row0

    def step(carry, xs):
        sigma, matched = carry
        t, byte_cols = xs  # byte_cols [P, B]
        r_planes = _r_planes(
            [
                [
                    jnp.take(plane, byte_cols[pid_ix[p]], axis=0).astype(jnp.float32)
                    for plane in planes
                ]
                for planes, p in zip(flat.tables, flat.seg_pipes)
            ]
        )
        active_p = (t < lens).astype(jnp.float32)  # [P, B]
        active_g = _dot(active_p.T, gp_j)  # [B, Gp]
        active_n = _by_segment(
            flat.seg_slots, [active_p[pid_ix[p]][:, None] for p in flat.seg_pipes]
        )
        sigma, matched = _flat_step_math(
            sigma, matched, r_planes, active_g, active_n, flat.sel, flat.bcast,
            flat.base_g, flat.s_g, iota_lo, iota_hi,
        )
        return (sigma, matched), None

    ts = jnp.arange(dataT.shape[0], dtype=jnp.int32)
    (sigma, matched), _ = jax.lax.scan(step, (sigma0, matched0), (ts, dataT))
    end_hit = _dot((sigma * flat.mend).astype(jnp.bfloat16), flat.sel)
    out = (matched + end_hit) > 0
    return out[:, : flat.n_groups] | flat.always[None, :]


def _flat_kernel(*refs, seg_pipes, seg_slots, seg_depth, pid_ix, n, gp_n, length, n_pipes):
    """Pallas kernel: one [Bt] row-block over all bytes, all banks fused.

    refs: dataT_p x P ([L, Bt]), len_p x P ([Bt, 1]), the table planes of
    every segment in turn (``seg_depth`` of them each), sel [N, Gp],
    bcast [Gp, N], init_sigma [1, N], mend [1, N], base_g [1, Gp],
    s_g [1, Gp], gp [P, Gp], out [Bt, Gp]."""
    it = iter(refs)
    dataT = [next(it) for _ in range(n_pipes)]
    lens = [next(it) for _ in range(n_pipes)]
    tables = [[next(it) for _ in range(depth)] for depth in seg_depth]
    sel_ref = next(it)
    bcast_ref = next(it)
    init_ref = next(it)
    mend_ref = next(it)
    base_ref = next(it)
    sg_ref = next(it)
    gp_ref = next(it)
    out_ref = next(it)

    bt = out_ref.shape[0]
    # Mosaic's tpu.iota is integer-only; cast after.
    iota_lo, iota_hi = _slot_digits(jax.lax.broadcasted_iota(jnp.int32, (1, n), 1))
    bytes_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, 256), 1)
    sel = sel_ref[:]
    bcast = bcast_ref[:]
    base_g = base_ref[:]
    s_g = sg_ref[:]
    gp = gp_ref[:]  # [P, Gp]

    def step(t, carry):
        sigma, matched = carry
        onehots = [
            (dataT[p][t, :][:, None] == bytes_iota).astype(jnp.bfloat16)
            for p in range(n_pipes)
        ]  # [Bt, 256] each
        r_planes = _r_planes(
            [
                [_dot(onehots[pid_ix[seg_pid]], plane[:]) for plane in planes]
                for planes, seg_pid in zip(tables, seg_pipes)
            ]
        )
        active = [
            (t < lens[i][:, 0][:, None]).astype(jnp.float32) for i in range(n_pipes)
        ]  # [Bt, 1] each
        active_g = _dot(jnp.concatenate(active, axis=1), gp)  # [Bt, P] @ [P, Gp], 0/1
        active_n = _by_segment(seg_slots, [active[pid_ix[p]] for p in seg_pipes])
        return _flat_step_math(
            sigma, matched, r_planes, active_g, active_n, sel, bcast,
            base_g, s_g, iota_lo, iota_hi,
        )

    sigma0 = jnp.broadcast_to(init_ref[:], (bt, n))
    matched0 = jnp.zeros((bt, gp_n), dtype=jnp.float32)
    sigma, matched = jax.lax.fori_loop(0, length, step, (sigma0, matched0))
    end_hit = _dot((sigma * mend_ref[:]).astype(jnp.bfloat16), sel)
    out_ref[:] = ((matched + end_hit) > 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _scan_flat_pallas(
    flat: FlatBank, dataT_list, lens_list, gp, interpret=False, name=None
):
    from jax.experimental import pallas as pl

    n, gp_n = flat.n_slots, flat.n_groups_padded
    length, bp = dataT_list[0].shape
    n_pipes = len(dataT_list)
    pids = sorted(set(flat.seg_pipes))
    pid_ix = {p: i for i, p in enumerate(pids)}

    seg_depth = tuple(len(planes) for planes in flat.tables)
    kernel = functools.partial(
        _flat_kernel,
        seg_pipes=flat.seg_pipes,
        seg_slots=flat.seg_slots,
        seg_depth=seg_depth,
        pid_ix=pid_ix,
        n=n,
        gp_n=gp_n,
        length=length,
        n_pipes=n_pipes,
    )
    in_specs = (
        [pl.BlockSpec((length, _BLOCK_B), lambda i: (0, i)) for _ in range(n_pipes)]
        + [pl.BlockSpec((_BLOCK_B, 1), lambda i: (i, 0)) for _ in range(n_pipes)]
        + [
            pl.BlockSpec((256, sn), lambda i: (0, 0))
            for sn, depth in zip(flat.seg_slots, seg_depth)
            for _ in range(depth)
        ]
        + [
            pl.BlockSpec((n, gp_n), lambda i: (0, 0)),
            pl.BlockSpec((gp_n, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, gp_n), lambda i: (0, 0)),
            pl.BlockSpec((1, gp_n), lambda i: (0, 0)),
            pl.BlockSpec((n_pipes, gp_n), lambda i: (0, 0)),
        ]
    )
    return pl.pallas_call(
        kernel,
        grid=(bp // _BLOCK_B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((_BLOCK_B, gp_n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, gp_n), jnp.int32),
        interpret=interpret,
        name=name,
    )(
        *dataT_list,
        *lens_list,
        *(plane for planes in flat.tables for plane in planes),
        flat.sel,
        flat.bcast,
        flat.init_sigma,
        flat.mend,
        flat.base_g,
        flat.s_g,
        gp,
    )


def scan_flat_bank(
    flat: FlatBank,
    data_by_pipe: dict[int, tuple[jnp.ndarray, jnp.ndarray]],
    interpret: bool | None = None,
    name: str | None = None,
) -> jnp.ndarray:
    """Fused scan of one bin. Returns matched [B, G_bin] bool. ``name``
    is the Pallas kernel's name in a device trace.

    Pallas kernel on TPU; XLA scan elsewhere. ``interpret=True`` forces
    the kernel through the Pallas interpreter (CPU kernel-logic tests).
    Buffers wider than _PALLAS_MAX_LEN (the width the bins' VMEM plan
    budgeted for) stream through the XLA formulation instead."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return scan_flat_xla(flat, data_by_pipe)
        pids_chk = sorted(set(flat.seg_pipes))
        if data_by_pipe[pids_chk[0]][0].shape[1] > _PALLAS_MAX_LEN:
            return scan_flat_xla(flat, data_by_pipe)
        interpret = False

    pids = sorted(set(flat.seg_pipes))
    d0 = data_by_pipe[pids[0]][0]
    b = d0.shape[0]
    bp = _round_up(max(b, _BLOCK_B), _BLOCK_B)
    dataT_list, lens_list = [], []
    for p in pids:
        d, ln = data_by_pipe[p]
        dataT_list.append(jnp.pad(d.astype(jnp.int32), ((0, bp - b), (0, 0))).T)
        lens_list.append(jnp.pad(ln.astype(jnp.int32), (0, bp - b))[:, None])
    gp = jnp.asarray(_group_pipe_onehot(flat, pids))
    out = _scan_flat_pallas(
        flat, tuple(dataT_list), tuple(lens_list), gp, interpret=interpret, name=name
    )
    return (out[:b, : flat.n_groups] != 0) | flat.always[None, :]
