"""Conv-segment matcher: every match position of every segment in ONE
MXU convolution, then gap-chaining as bitmap algebra.

Where the DFA bank (``ops/dfa.py``) spends ``256·S·G`` MACs *per input
byte* (a sequential ``lax.scan``), this tier matches all fixed-length
byte-class segments (``compiler/segments.py``) for **all start positions
at once**:

1. **embed**: bytes → ``[T, Lp, k·C]`` channel planes built from pure VPU
   comparisons (nibble one-hots, class-interval tests, a constant ones
   plane) — no gathers, no 256-wide one-hot. Each byte stands with its
   k − 1 right-hand neighbours, so the C planes are compared once over
   ``[T, Lp, k]`` (step 2 says what k is).
2. **conv**: one ``conv_general_dilated``. Each segment position
   contributes exactly 2 when its byte matches (hi+lo
   nibble hits for product classes, weight-2 indicator otherwise, the
   ones plane for padding), so ``out == 2W`` ⇔ the segment matches at
   that window start. This is the classic exact-match-as-threshold
   formulation: a DFA transition needs a table lookup; an equality test
   is just arithmetic, and arithmetic is what the systolic array does.
   The kernel is ``[W, C, N]`` and a tap contracts over the block's C
   embed channels alone (16 to 42 for CRS and a site's feed) of the
   MXU's 128-deep contraction, so ``k = 128 // C`` taps ride one
   contraction (``conv_tap_packing``): the embed holds positions
   p .. p + k − 1 side by side on the channel axis, the kernel takes zero
   taps up to ``k·J``, ``J = ceil(W / k)``, and is reshaped to
   ``[J, k·C, N]``, and the conv is dilated by k, so tap j reads
   positions p + k·j .. p + k·j + k − 1. The sums are the same whole
   numbers (a zero tap adds nothing). A block of more than 64 channels
   has k = 1 and traces the plain conv, operation for operation.
   PERF.md §5–§6 (PR 47) have the blocks' statics and the readings.
3. **chain**: gap constraints as bitmap algebra on ``[T, Q, ·]``
   blocks. Multi-element branches starting with a segment (the common
   shape: literal token, then gaps/segments) are SUFFIX-DEDUPED: the
   ops after the first segment evaluate right-to-left once per distinct
   suffix, and each branch collapses to one AND-any against its first
   segment's conv column. No cumulative op is a scan primitive
   (``jnp.cumsum``/``lax.cummax`` lower to reduce-window on TPU, which
   profiled at a quarter of the block's runtime). Which form a gap
   takes, by what the trace can see:

   - any-gaps (``.{lo,hi}``, ``.*``): window-ORs as doubling shifts
     (``_spread_or``), at every width;
   - bounded class gaps (``[^>]{0,60}``): shift-unrolled ORs up to a
     window of 8, a windowed ``min`` of the NCE counts past that
     (``_window_min``), at every width, both directions;
   - unbounded class gaps (``\\s*``, ``[^&]*``), backward (tier b) and
     forward in a structure whose ``[T, Q, ns]`` block is under
     ``_REACH_MIN_ELEMS`` elements: the NCE latch, a running ``min`` in
     log2(Q) shifted passes (``_latch_min``: 10 at Q = 514, 12 at
     2,050) over an ``int32`` block. While that block is small the
     compiler keeps it in fast memory and a pass is next to nothing
     (CRS's structures, ns ≤ 16 and mostly 1–3: 4 MB at ``32x2048``);
     a block of tens of MB is slower there in proportion, and one that
     does not stay there goes through HBM every pass: 0.34 ms a pass on
     a site feed's 1,500 suffixes under one structure at ``32x512``
     (49 MB: 8 of its 14 fused passes write to HBM in the optimized HLO);
   - unbounded FORWARD class gaps of a structure whose block holds
     ``_REACH_MIN_ELEMS`` elements or more: reachability matmuls
     (``_reach_gap``). A row's class runs do not depend on the column,
     so one ``[B, B]`` 0/1 table a block of B = 128 positions says which
     positions reach which, a batched matmul ORs the block's hits along
     the runs, and a second, tiny one carries a run from block to block:
     one pass of the MXU and about three of a ``bool`` block where the
     latch makes ten to twelve of an ``int32`` one, and no step with
     size. Measured on a v5e (PR 44, ``hack/reach_gap_probe.py``, an
     application alone, latch / matmuls in ms): 4.2 M elements
     (``[32, 2050, 64]``, ``[16, 514, 500]``) 0.13 / 0.13; 6.6 M
     (``[32, 2050, 100]``) 0.18 / 0.21; 8.4 M (``[32, 2050, 128]``)
     0.30 / 0.13; 12.3 M (``[16, 514, 1500]``) 0.42 / 0.28; 19.7 M
     (``[32, 2050, 300]``) 2.04 / 0.45; 98 M (``[32, 2050, 1500]``)
     20.1 / 1.9; at crs-lite's 16 columns the latch wins by 1.2–1.5x
     (0.07 / 0.10 at 2,050 positions). In a feed's matcher, where other
     blocks compete for the fast memory: ``[16, 514, 500]`` 0.1 / 0.19
     (the latch stays); ``[16, 514, 1500]`` 1.2 / 0.28; the fourteen gaps
     of a ``32x2048`` window's tiles (``[32, 2050, 113..342]``) 0.32
     each as matmuls, where the three structures they stand in cost 21.0
     ms with latches and 6.5 without.

   The prefix SUM under all of the class gaps, the NCE counts, rides the
   MXU at every width too: blocked triangular matmuls
   (``_excl_prefix_sum``, scope ``cko.seg.nce``).

Conv output columns are PERMUTED (and duplicated when shared) at trace
time so every chain/final/solo consumer reads a contiguous slice of
``m_all`` — arbitrary channel-list indexing is a minor-axis gather,
which serializes on TPU and cost ~half the block before the rewrite.

Position space: padded index ``p`` covers a front NUL pad (``p = 0``,
which makes start-of-input read as a non-word byte for ``\\b``) plus the
buffer; chain bitmaps say "the next element may start at ``p``". Match
validity is enforced per segment (``p + n_real <= 1 + len``) and at the
final reduce, so gap travel through the zero tail can never fabricate a
match.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.re_parser import ALL_BYTES
from ..compiler.segments import Branch, Gap, Seg, SegmentPlan

# Positions a triangular table of the NCE prefix counts spans: a block's
# total (≤ 256) is a whole number in bf16, and the table is O(B²)
# whatever Q a request's body gives the tier.
_PREFIX_BLOCK = 256

# Positions a block of the class-gap reachability matmul spans
# (``_reach_tables`` / ``_reach_gap``): the MXU's own edge on a v5e, so a
# block's [B, B] table is one pass deep, Q = L + 2 pads by at most 127
# positions and the tables are half of what 256 would hold.
_REACH_BLOCK = 128
# Elements of a suffix structure's ``[T, Q, ns]`` block (rows and
# positions as ``match_segment_block`` is traced with them, a chunk's
# under row chunks; ns the structure's columns, a tile's share under
# column tiles) from which its unbounded forward class gaps take the
# reachability matmul instead of ``_latch_min``'s log2(Q) passes. Both
# forms' cost follows the block's BYTES, not its columns: the latch moves
# ten to twelve times eight bytes an element, through fast memory while
# the compiler keeps the ``int32`` block there (memory space ``S(1)`` on
# its layout in the optimized HLO) and through HBM past that; the matmul
# form moves about four times one byte and pays a pad, a table and a copy
# whatever the size. On a v5e (PR 44; the module docstring has the
# readings) the two tie from 4.2 M to 6.6 M elements alone, the latch is
# the cheaper at 4.1 M in a matcher, the matmuls are from 8.4 M on, by
# 2x, and by 4x to 10x once the block has left fast memory (12 M elements
# in a feed's matcher, 20 M alone). This is the middle of the tie, not a
# measured point: no structure of a benchmarked matcher lies between 4.2 M
# and 7.4 M. crs-lite's widest structure is 16 columns: 1.0 M elements at
# ``32x2048``.
_REACH_MIN_ELEMS = 6 << 20
# The level beneath a structure's scope that the matmul form's operations
# stand under: ``cko.seg.suffix/b<block>.st<i>/reach``.
_REACH_SCOPE = "reach"

# Depth of the MXU's contraction: what one pass of the systolic array
# multiplies at once. 128 on a v5e, the one generation this repo has
# measured; a 256-deep array (v6e) would pack twice the taps, and the
# constant would then follow the backend's device kind.
_MXU_DEPTH = 128


# ---------------------------------------------------------------------------
# Host-side build: plans → channel/kernel spec
# ---------------------------------------------------------------------------


def _intervals(mask: int) -> list[tuple[int, int]]:
    """Byte mask → sorted inclusive intervals."""
    out: list[tuple[int, int]] = []
    b = 0
    while b < 256:
        if mask >> b & 1:
            start = b
            while b < 256 and mask >> b & 1:
                b += 1
            out.append((start, b - 1))
        else:
            b += 1
    return out


def _product_parts(mask: int) -> tuple[tuple[int, ...], tuple[int, ...]] | None:
    """If ``mask`` is exactly ``hiSet x loSet``, return the nibble sets."""
    his: set[int] = set()
    los: set[int] = set()
    count = 0
    for byte in range(256):
        if mask >> byte & 1:
            his.add(byte >> 4)
            los.add(byte & 15)
            count += 1
    if count and len(his) * len(los) == count:
        return tuple(sorted(his)), tuple(sorted(los))
    return None


@dataclass(frozen=True)
class SegmentSpec:
    """Hashable static program for one pipeline's conv block."""

    w: int  # kernel width
    n_seg: int  # conv output channels
    channels: tuple  # embed plan: ('hi',k)|('lo',k)|('one',)|('ind', intervals)
    # per conv channel: (n_lead, n_real)
    seg_meta: tuple[tuple[int, int], ...]
    # per branch: (group, chan_elements) where chan_elements is a tuple of
    #   ('seg', chan) | ('gapany', lo, hi|-1) | ('gapcls', intervals, lo, hi|-1)
    # plus anchors
    branches: tuple[tuple[int, tuple, bool, bool], ...]
    always: tuple[int, ...]  # group ids that always match
    n_groups: int


@jax.tree_util.register_pytree_node_class
@dataclass
class SegmentBlock:
    """Device arrays + static spec for one pipeline's conv matcher.

    The conv ``kernel`` is a LEAF (runtime operand); ``spec`` is the aux
    and is genuinely structural — the chain programs it encodes ARE the
    traced computation, so two rulesets share this block's executable
    only when their specs match (shape-canonical executable reuse,
    ``engine/compile_cache.py``). DFA-routed rules have no such static:
    prefer them when authoring synthetic load that must share
    executables across rulesets."""

    kernel: jnp.ndarray  # [W, C, N] bf16
    spec: SegmentSpec

    def tree_flatten(self):
        return (self.kernel,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(children[0], spec)

    @property
    def n_groups(self) -> int:
        return self.spec.n_groups


def build_segment_block(plans: list[SegmentPlan]) -> SegmentBlock:
    """Stack group plans (group id = list index) into one conv block."""
    channels: list[tuple] = [("hi", k) for k in range(16)]
    channels += [("lo", k) for k in range(16)]
    channels.append(("one",))
    ch_index: dict[tuple, int] = {c: i for i, c in enumerate(channels)}

    def indicator(mask: int) -> int:
        key = ("ind", tuple(_intervals(mask)))
        if key not in ch_index:
            ch_index[key] = len(channels)
            channels.append(key)
        return ch_index[key]

    # Intern segments; collect branch programs. The intern key must be
    # (classes, geometry): two segments with identical byte-class
    # sequences but different lead/trail splits (e.g. `(ALL,)` as a
    # one-byte lead context vs as a one-byte trailing lookahead) need
    # DISTINCT ids — seg_meta is per id, and sharing a column across
    # geometries made every later consumer inherit the first one's
    # shifts (an order-dependent false negative caught by the host
    # fallback parity gate on CRS 942120).
    seg_ids: dict[tuple, int] = {}
    seg_meta: list[tuple[int, int]] = []
    seg_classes: list[tuple[int, ...]] = []
    branches: list[tuple[int, tuple, bool, bool]] = []
    always: list[int] = []
    w = 1
    for gid, plan in enumerate(plans):
        if plan.always:
            always.append(gid)
        for br in plan.branches:
            prog: list[tuple] = []
            for el in br.elements:
                if isinstance(el, Seg):
                    key = (el.classes, el.n_lead, el.n_real)
                    if key not in seg_ids:
                        seg_ids[key] = len(seg_classes)
                        seg_classes.append(el.classes)
                        seg_meta.append((el.n_lead, el.n_real))
                        w = max(w, len(el.classes))
                    prog.append(("seg", seg_ids[key]))
                else:
                    hi = -1 if el.hi is None else el.hi
                    if el.mask == ALL_BYTES:
                        prog.append(("gapany", el.lo, hi))
                    else:
                        prog.append(
                            ("gapcls", tuple(_intervals(el.mask)), el.lo, hi)
                        )
            branches.append((gid, tuple(prog), br.anchored_start, br.anchored_end))

    n = max(1, len(seg_classes))
    # First pass: intern every indicator channel so the kernel can be
    # allocated at its final channel count.
    products: dict[int, tuple] = {}
    for classes in seg_classes:
        for mask in classes:
            if mask not in products:
                products[mask] = _product_parts(mask)
            if products[mask] is None:
                indicator(mask)
    # Kernel: every position of every channel contributes exactly 2 on match.
    kernel = np.zeros((w, len(channels), n), dtype=np.float32)
    for ci, classes in enumerate(seg_classes):
        for pos in range(w):
            if pos < len(classes):
                mask = classes[pos]
                parts = products[mask]
                if parts is not None:
                    his, los = parts
                    for h in his:
                        kernel[pos, ch_index[("hi", h)], ci] += 1.0
                    for lo in los:
                        kernel[pos, ch_index[("lo", lo)], ci] += 1.0
                else:
                    kernel[pos, indicator(mask), ci] += 2.0
            else:
                kernel[pos, ch_index[("one",)], ci] += 2.0
    # Prune embed channels no segment references (e.g. nibble planes of
    # bytes that never appear) — shrinks both the embed and the matmul K.
    used = kernel.any(axis=(0, 2))
    kernel = kernel[:, used, :]
    channels = [c for c, u in zip(channels, used) if u]

    spec = SegmentSpec(
        w=w,
        n_seg=n,
        channels=tuple(channels),
        seg_meta=tuple(seg_meta) or ((0, 1),),
        branches=tuple(branches),
        always=tuple(always),
        n_groups=len(plans),
    )
    return SegmentBlock(kernel=jnp.asarray(kernel, dtype=jnp.bfloat16), spec=spec)


# ---------------------------------------------------------------------------
# Device-side evaluation
# ---------------------------------------------------------------------------


def conv_tap_packing(spec: SegmentSpec) -> tuple[int, int]:
    """``(k, J)``: the taps of the block's kernel that ride one contraction
    of the conv, and the taps the conv is left with. ``k`` is as many as
    fill the MXU's depth with the block's C channels (at most the W there
    are, 1 for a block of more than half the depth: the plain conv);
    ``J = ceil(W / k)``."""
    k = max(1, min(spec.w, _MXU_DEPTH // len(spec.channels)))
    return k, -(-spec.w // k)


def conv_passes(spec: SegmentSpec) -> int:
    """Passes of the MXU's depth a conv of this block makes an output tile:
    its taps times the ``_MXU_DEPTH``-deep slices of a tap's contraction."""
    k, taps = conv_tap_packing(spec)
    return taps * -(-k * len(spec.channels) // _MXU_DEPTH)


def conv_fill(spec: SegmentSpec) -> float:
    """The share of the MXU's depth that the block's conv fills a pass."""
    k, _taps = conv_tap_packing(spec)
    depth = k * len(spec.channels)
    return depth / (_MXU_DEPTH * -(-depth // _MXU_DEPTH))


def _channel_plane(chan: tuple, dpad: jnp.ndarray) -> jnp.ndarray:
    kind = chan[0]
    if kind == "hi":
        return (dpad >> 4) == chan[1]
    if kind == "lo":
        return (dpad & 15) == chan[1]
    if kind == "one":
        return jnp.ones_like(dpad, dtype=bool)
    ivs = chan[1]  # ('ind', intervals)
    acc = jnp.zeros_like(dpad, dtype=bool)
    for lo, hi in ivs:
        acc = acc | ((dpad >= lo) & (dpad <= hi)) if lo != hi else acc | (dpad == lo)
    return acc


def _embed_taps(spec: SegmentSpec, data: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Step 1: the padded bytes ``[T, 1+L+W']`` and the channel planes
    ``[T, span, k·C]`` bf16 the conv reads, each byte beside its k − 1
    right-hand neighbours (``conv_tap_packing``). The neighbours are stacked
    as BYTES and the C planes compared once over ``[T, span, k]``: k shifted
    slices of the planes' stack cost a v5e's compiler a relayout copy a
    plane (PERF.md §6, PR 47)."""
    pack, taps = conv_tap_packing(spec)
    span = data.shape[1] + 2 + pack * (taps - 1)  # positions a packed tap starts at
    # Front NUL pad (position 0) + right slack so every window is full:
    # W, up to ``pack * taps`` with the kernel's zero taps.
    dpad = jnp.pad(data, ((0, 0), (1, pack * taps))).astype(jnp.int32)
    near = dpad if pack == 1 else jnp.stack([dpad[:, r : r + span] for r in range(pack)], axis=-1)
    planes = [_channel_plane(c, near) for c in spec.channels]
    embed = jnp.stack(planes, axis=-1).astype(jnp.bfloat16).reshape(data.shape[0], span, -1)
    return dpad, embed


def _conv_taps(spec: SegmentSpec, embed: jnp.ndarray, kernel_p: jnp.ndarray) -> jnp.ndarray:
    """Step 2: ``[T, Q, N2]`` bf16 sums of ``_embed_taps``' planes under the
    ``[W, C, N2]`` kernel, ``pack`` taps a contraction."""
    pack, taps = conv_tap_packing(spec)
    if pack > 1:  # zero taps up to pack·taps, then ``pack`` taps a contraction: [J, k·C, N2]
        kernel_p = jnp.pad(kernel_p, ((0, pack * taps - spec.w), (0, 0), (0, 0)))
        kernel_p = kernel_p.reshape(taps, pack * len(spec.channels), -1)
    # bf16 accumulation is exact here (integer partial sums ≤ 2W = 52
    # ≪ 256) and halves the conv-output HBM traffic — the threshold is
    # fused into each consumer, so every chain stage reads `out`, not a
    # materialized bool.
    return jax.lax.conv_general_dilated(
        embed,
        kernel_p,
        window_strides=(1,),
        padding="VALID",
        rhs_dilation=(pack,),
        dimension_numbers=("NWC", "WIO", "NWC"),
        preferred_element_type=jnp.bfloat16,
    )


def _in_class(ivs: tuple, dpad: jnp.ndarray) -> jnp.ndarray:
    acc = jnp.zeros_like(dpad, dtype=bool)
    for lo, hi in ivs:
        acc = acc | ((dpad >= lo) & (dpad <= hi)) if lo != hi else acc | (dpad == lo)
    return acc


def _rshift(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Shift right along axis 1, zero/False fill."""
    if k == 0:
        return x
    return jnp.pad(x, ((0, 0), (k, 0)))[:, : x.shape[1]]


def _rshift3(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Shift right along axis 1 of a [T, Q, NB] array, zero fill."""
    if k == 0:
        return x
    return jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, : x.shape[1]]


def _lshift3(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Shift left along axis 1 of a [T, Q, NB] array, zero fill:
    out[:, p] = x[:, p + k]."""
    if k == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, k), (0, 0)))[:, k:]


def _shift3_fill(x: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    """Shift along axis 1 of [T, Q, NB]: out[:, p] = x[:, p + k] (k > 0
    pulls from the right, k < 0 from the left), filled with ``fill``."""
    if k == 0:
        return x
    if k > 0:
        return jnp.pad(x, ((0, 0), (0, k), (0, 0)), constant_values=fill)[:, k:]
    return jnp.pad(x, ((0, 0), (-k, 0), (0, 0)), constant_values=fill)[:, : x.shape[1]]


def _spread_or(x: jnp.ndarray, lo: int, hi: int, forward: bool) -> jnp.ndarray:
    """OR-spread along axis 1: out[p] = ∃d ∈ [lo, hi (or ∞ if hi<0)]:
    x[p + d] (forward) or x[p - d] (backward). Log-shift passes — TPU
    has no fast scan lowering (cumsum/cummax become reduce-window), and
    Q is tiny, so log2(Q) elementwise ORs win."""
    q = x.shape[1]
    sgn = 1 if forward else -1
    assert hi < 0 or hi >= lo, f"empty gap range [{lo}, {hi}]"
    if hi < 0:
        # Unbounded: suffix/prefix OR, then shift by lo.
        y = x
        k = 1
        while k < q:
            y = y | _shift3_fill(y, sgn * k, False)
            k *= 2
        return _shift3_fill(y, sgn * lo, False)
    width = hi - lo + 1
    # OR over a window of `width`: doubling windows, then one patch-up.
    y = x
    span = 1  # y[p] == OR of x[p .. p + span-1] (direction-adjusted)
    while span * 2 <= width:
        y = y | _shift3_fill(y, sgn * span, False)
        span *= 2
    if span < width:
        y = y | _shift3_fill(y, sgn * (width - span), False)
    return _shift3_fill(y, sgn * lo, False)


def _latch_min(vals: jnp.ndarray, big, forward: bool) -> jnp.ndarray:
    """Running min along axis 1 (suffix-min if forward, prefix-min if
    backward) via log-shift passes — avoids reduce-window."""
    q = vals.shape[1]
    sgn = 1 if forward else -1
    y = vals
    k = 1
    while k < q:
        y = jnp.minimum(y, _shift3_fill(y, sgn * k, big))
        k *= 2
    return y


def _window_min(vals: jnp.ndarray, lo: int, hi: int, big, forward: bool) -> jnp.ndarray:
    """Windowed min along axis 1: out[p] = min over d ∈ [lo, hi] of
    vals[p + d] (forward) / vals[p - d] (backward). Doubling spans plus
    one patch-up pass — O(log(hi - lo)) elementwise mins, the min-domain
    mirror of ``_spread_or``."""
    sgn = 1 if forward else -1
    width = hi - lo + 1
    y = vals
    span = 1
    while span * 2 <= width:
        y = jnp.minimum(y, _shift3_fill(y, sgn * span, big))
        span *= 2
    if span < width:
        y = jnp.minimum(y, _shift3_fill(y, sgn * (width - span), big))
    return _shift3_fill(y, sgn * lo, big)


def _lshift_fill(x: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    if k == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, k)), constant_values=fill)[:, k:]


def _excl_prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """out[..., p] = sum of x[..., :p], as f32. ``x`` is bf16 [..., N] of
    whole numbers ≤ 256 (exact in bf16); every partial sum is a whole
    number accumulated in f32, so the result is exact while a row's total
    stays under 2**24.

    Blocked so that no table grows with N: inside a block of B = 256
    positions one matmul with the strict upper-triangular [B, B] table;
    across blocks the same function over the block totals. A total is at
    most 256·256, which bf16 does not hold: it goes up as its two
    base-256 digits (each ≤ 256), stacked into one call. N ≤ B is one
    block: one [N, N] matmul and nothing else."""
    *lead, n = x.shape
    b = min(n, _PREFIX_BLOCK)
    nb = -(-n // b)
    if nb * b != n:
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, nb * b - n)])
    xb = x.reshape(*lead, nb, b)
    tri = jnp.asarray(np.triu(np.ones((b, b), dtype=np.float32), 1), dtype=jnp.bfloat16)  # [p', p]: p' < p
    within = jnp.dot(xb, tri, preferred_element_type=jnp.float32)  # [..., NB, B]
    if nb == 1:
        return within.reshape(*lead, n)
    totals = within[..., -1] + xb[..., -1]  # [..., NB] f32
    hi = jnp.floor(totals / 256)
    before = _excl_prefix_sum(jnp.stack([hi, totals - 256 * hi]).astype(jnp.bfloat16))
    offset = 256 * before[0] + before[1]  # positions' worth before each block
    return (within + offset[..., None]).reshape(*lead, nb * b)[..., :n]


def _excl_prefix_count(mask: jnp.ndarray) -> jnp.ndarray:
    """bool [..., Q] -> int32 [..., Q]: how many of mask[..., :p] are
    set: the inclusive running count less the element itself, bit for
    bit, on the MXU."""
    return _excl_prefix_sum(mask.astype(jnp.bfloat16)).astype(jnp.int32)


def _reach_tables(nce: jnp.ndarray, big) -> tuple[jnp.ndarray, ...]:
    """What every unbounded forward gap of one class reads, from the
    class's NCE table [T, Q] alone (a row's class runs do not depend on
    the column): ``within`` [T, NB, B, B] bf16, 1 where j ≥ i and the
    bytes [i, j) of the block are all in the class; ``cross`` [T, NB, B]
    bool, the run from p reaches the start of the next block; ``beyond``
    [T, NB, NB] bf16, 1 at (b, b') where b' > b and the run from the
    start of block b + 1 reaches the start of block b'. Positions past Q
    count ``big``, which equals no real count: no run enters them."""
    t, q = nce.shape
    b = _REACH_BLOCK
    nb = -(-q // b)
    padded = jnp.pad(nce, ((0, 0), (0, (nb + 1) * b - q)), constant_values=big)
    blocks = padded[:, : nb * b].reshape(t, nb, b)
    upper = jnp.asarray(np.triu(np.ones((b, b), dtype=bool)))  # [i, j]: j ≥ i
    within = ((blocks[:, :, :, None] == blocks[:, :, None, :]) & upper).astype(jnp.bfloat16)
    starts = padded[:, ::b]  # [T, NB + 1]: the count at each block's start
    cross = blocks == starts[:, 1:, None]
    later = jnp.asarray(np.triu(np.ones((nb, nb), dtype=bool), 1))  # [b, b']: b' > b
    beyond = ((starts[:, 1:, None] == starts[:, None, :nb]) & later).astype(jnp.bfloat16)
    return within, cross, beyond


def _reach_gap(x: jnp.ndarray, tables: tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """out[t, p, n] = ∃ p' ≥ p: bytes [p, p') ∈ class ∧ x[t, p', n]: the
    NCE latch (``_latch_min(where(x, nce, big)) == nce``) bit for bit, as
    0/1 matmuls of ``_reach_tables``. Inside a block one batched
    [B, B] x [B, ns] product; across blocks, a block's first row says
    whether a run that enters it from the left finds a hit, and one
    [NB, NB] x [NB, ns] product carries that to every block before it.
    Sums are at most B and only their sign is read: exact in bf16 x bf16
    -> f32, which is one pass of the MXU."""
    within, cross, beyond = tables
    t, q, ns = x.shape
    nb, b = cross.shape[1:]
    xb = jnp.pad(x, ((0, 0), (0, nb * b - q), (0, 0))).reshape(t, nb, b, ns)
    local = jnp.einsum(
        "tbij,tbjn->tbin", within, xb.astype(jnp.bfloat16), preferred_element_type=jnp.float32
    ) > 0
    entered = local[:, :, 0].astype(jnp.bfloat16)  # [T, NB, ns]: from a block's first position
    ahead = jnp.einsum(
        "tbc,tcn->tbn", beyond, entered, preferred_element_type=jnp.float32
    ) > 0  # a run that leaves block b to the right finds a hit
    out = local | (cross[..., None] & ahead[:, :, None, :])
    return out.reshape(t, nb * b, ns)[:, :q]


def _branch_signature(spec: SegmentSpec, prog: tuple, a_start: bool, a_end: bool):
    """Branches with identical signatures run as one batched chain: the op
    sequence with all *static shift amounts* (n_lead/n_real/gap bounds and
    gap classes) — only the conv channel ids differ within a bucket."""
    sig: list[tuple] = []
    for el in prog:
        if el[0] == "seg":
            n_lead, n_real = spec.seg_meta[el[1]]
            sig.append(("seg", n_lead, n_real))
        else:
            sig.append(el)  # gap params are the signature
    return (tuple(sig), a_start, a_end)


def _suffix_structures(spec: SegmentSpec) -> tuple[dict, dict]:
    """Tier (a)'s distinct suffixes and the structures they stand under:
    ``suffix_ids`` (the ops after a branch's first segment, its end
    anchor) -> id in branch order, and ``struct``, the suffixes' op
    sequence with channel ids dropped (static shifts and gap classes
    kept) -> its members ``(suffix key, id)``: one [T, Q, len(members)]
    bitmap and one right-to-left pass a structure."""
    suffix_ids: dict[tuple, int] = {}
    for _gid, prog, _a_start, a_end in spec.branches:
        if len(prog) >= 2 and prog[0][0] == "seg":
            suffix_ids.setdefault((prog[1:], a_end), len(suffix_ids))
    struct: dict[tuple, list[tuple[tuple, int]]] = {}
    for skey, sid in suffix_ids.items():
        ops, a_end = skey
        sig = tuple(("seg", *spec.seg_meta[el[1]]) if el[0] == "seg" else el for el in ops)
        struct.setdefault((sig, a_end), []).append((skey, sid))
    return suffix_ids, struct


def _reach_classes(sig_ops: tuple, elems: int) -> list[tuple]:
    """The classes of the unbounded forward class gaps that a suffix
    structure whose ``[T, Q, ns]`` block holds ``elems`` elements runs as
    reachability matmuls, one entry a gap: none under ``_REACH_MIN_ELEMS``."""
    if elems < _REACH_MIN_ELEMS:
        return []
    return [op[1] for op in sig_ops if op[0] == "gapcls" and op[3] < 0]


def reach_gap_count(spec: SegmentSpec, rows: int, positions: int) -> int:
    """Class-gap operations ``match_segment_block`` runs as matmuls for
    this spec over ``rows`` rows of ``positions`` = L + 2 chain positions
    (``seg_plan.reach_gaps``)."""
    _ids, struct = _suffix_structures(spec)
    return sum(
        len(_reach_classes(sig[0], rows * positions * len(members)))
        for sig, members in struct.items()
    )


class _ConvColumns:
    """The conv output columns a set of branches allocates — ``len(col_order)``
    as ``match_segment_block`` will build it — counted branch by branch,
    so that a block's groups can be dealt into column tiles."""

    def __init__(self, spec: SegmentSpec):
        self.spec = spec
        self.n = 0
        self._suffixes: set[tuple] = set()
        self._finals: set[tuple] = set()

    def cost(self, branches) -> tuple[int, set, set]:
        """What taking ``branches`` (entries of ``spec.branches``) would
        add; nothing is kept until ``take``."""
        n, suffixes, finals = 0, set(), set()
        for _gid, prog, a_start, a_end in branches:
            if len(prog) >= 2 and prog[0][0] == "seg":
                # suffix-deduped chains: one column per seg element per
                # DISTINCT suffix (grouping by structural signature only
                # changes slicing, not the total).
                skey = (prog[1:], a_end)
                if skey not in self._suffixes and skey not in suffixes:
                    suffixes.add(skey)
                    n += sum(1 for el in prog[1:] if el[0] == "seg")
                # finals tier: one column per DISTINCT (suffix, geometry,
                # anchor, first-segment) — cross-rule duplicates share it.
                chan = prog[0][1]
                fkey = (skey, *self.spec.seg_meta[chan], a_start, chan)
                if fkey not in self._finals and fkey not in finals:
                    finals.add(fkey)
                    n += 1
            else:
                # signature-bucketed tier: one column per seg element.
                n += sum(1 for el in prog if el[0] == "seg")
        return n, suffixes, finals

    def take(self, cost: tuple[int, set, set]) -> None:
        n, suffixes, finals = cost
        self.n += n
        self._suffixes |= suffixes
        self._finals |= finals


def conv_n2_cols(spec: SegmentSpec) -> int:
    """Duplicated/permuted conv output column count — ``len(col_order)``
    as ``match_segment_block`` will build it. The budget in
    ``segment_tier_hits`` must use this, not ``kernel.shape[2]``: shared
    segments are duplicated per consumer slice, so N2 ≥ N and the conv
    output is ``[T, Q, N2]``, which is what actually occupies HBM."""
    cols = _ConvColumns(spec)
    cols.take(cols.cost(spec.branches))
    return max(1, cols.n)


def _branches_by_group(spec: SegmentSpec) -> list[list[tuple]]:
    by_group: list[list[tuple]] = [[] for _ in range(spec.n_groups)]
    for br in spec.branches:
        by_group[br[0]].append(br)
    return by_group


def widest_group_cols(spec: SegmentSpec) -> int:
    """Conv columns of the block's widest single group: the narrowest a
    column tile of this block can be (a group's chained pieces, its
    suffix and its finals stay in one tile)."""
    widest = 1
    for branches in _branches_by_group(spec):
        widest = max(widest, _ConvColumns(spec).cost(branches)[0])
    return widest


def cut_column_tiles(spec: SegmentSpec, max_cols: int) -> list[tuple[int, int, int]]:
    """Deal the block's groups, in order, into column tiles ``(g0, g1,
    conv columns)`` of at most ``max_cols`` columns each, cut along group
    boundaries: a group's branches share one tile, so its chained pieces
    and the suffix they end in are computed where its finals read them.
    A suffix that groups of two tiles share costs its columns in both.
    A single group wider than ``max_cols`` is a tile of its own (the
    caller sees it from the count)."""
    tiles: list[tuple[int, int, int]] = []
    cols, g0 = _ConvColumns(spec), 0
    for gid, branches in enumerate(_branches_by_group(spec)):
        cost = cols.cost(branches)
        if gid > g0 and cols.n + cost[0] > max_cols:
            tiles.append((g0, gid, max(1, cols.n)))
            cols, g0 = _ConvColumns(spec), gid
            cost = cols.cost(branches)
        cols.take(cost)
    tiles.append((g0, spec.n_groups, max(1, cols.n)))
    return tiles


def tile_spec(spec: SegmentSpec, g0: int, g1: int) -> SegmentSpec:
    """The block's program for groups ``g0:g1`` alone, their ids counted
    from 0. The kernel is the block's own: a tile's ``col_order`` picks
    the columns its branches read."""
    if (g0, g1) == (0, spec.n_groups):
        return spec
    return SegmentSpec(
        w=spec.w,
        n_seg=spec.n_seg,
        channels=spec.channels,
        seg_meta=spec.seg_meta,
        branches=tuple(
            (gid - g0, prog, a_start, a_end)
            for gid, prog, a_start, a_end in spec.branches
            if g0 <= gid < g1
        ),
        always=tuple(gid - g0 for gid in spec.always if g0 <= gid < g1),
        n_groups=g1 - g0,
    )


@partial(jax.jit, static_argnames=("spec", "block_index"))
def match_segment_block(
    kernel: jnp.ndarray,  # [W, C, N] bf16
    spec: SegmentSpec,
    data: jnp.ndarray,  # [T, L] uint8 (zero padded past lengths)
    lengths: jnp.ndarray,  # [T] int32
    block_index: int = 0,
) -> jnp.ndarray:
    """Returns group hits [T, n_groups] bool. ``block_index`` is the
    block's index in its model: it names the suffix structures in a device
    trace (``cko.seg.suffix/b<block>.st<i>``,
    observability/device_scopes.py) and changes no operation."""
    t, ln = data.shape
    w = spec.w
    q = ln + 2  # chain positions: window starts 0 .. L+1
    with jax.named_scope("cko.seg.embed"):
        # 1. embed: channel planes from comparisons only.
        dpad, embed = _embed_taps(spec, data)

    # --- static chain program (pure Python at trace time) ---
    # Two tiers:
    #
    # (a) seg-first multi-element branches (the vast majority: literal
    #     token then gaps/segments) run on the SUFFIX-DEDUPED path: the
    #     program after the first segment is computed right-to-left ONCE
    #     per distinct suffix as a [T, Q, NS] bitmap (NS = #distinct
    #     suffixes, usually ~1), then every branch reduces to ONE
    #     AND-any over its first segment's m_all column. v2 ran the
    #     whole 6-op program batched over NB branch columns — ~6 passes
    #     over an [T, Q, NB] block per bucket; suffix dedup makes the
    #     per-branch work a single read of its m_all column.
    #
    # (b) everything else (solo segments, gap-first branches) keeps the
    #     signature-bucketed batched program of v2.
    old_path: list[int] = []
    chain_first: list[int] = []
    for bi, (_gid, prog, _a_start, _a_end) in enumerate(spec.branches):
        if len(prog) >= 2 and prog[0][0] == "seg":
            chain_first.append(bi)
        else:
            old_path.append(bi)

    buckets: dict[tuple, list[int]] = {}
    for bi in old_path:
        gid, prog, a_start, a_end = spec.branches[bi]
        buckets.setdefault(_branch_signature(spec, prog, a_start, a_end), []).append(bi)

    suffix_ids, struct = _suffix_structures(spec)
    finals: dict[tuple, list[tuple[int, int]]] = {}
    for bi in chain_first:
        gid, prog, a_start, a_end = spec.branches[bi]
        sid = suffix_ids[(prog[1:], a_end)]
        seg_chan = prog[0][1]
        n_lead, n_real = spec.seg_meta[seg_chan]
        finals.setdefault((sid, n_lead, n_real, a_start), []).append((bi, seg_chan))

    # --- conv column layout ---
    # Every consumer below reads a CONTIGUOUS slice of the conv output:
    # arbitrary channel-list indexing is a gather along the minor axis,
    # which serializes on TPU and was measured at ~half the block's
    # runtime. Instead the *kernel* columns are permuted (and duplicated
    # where two consumers share a segment) at trace time — the "gather"
    # rides the MXU inside the conv, and m_all is born in consumer order.
    col_order: list[int] = []

    def alloc(chs: list[int]) -> tuple[int, int]:
        start = len(col_order)
        col_order.extend(chs)
        return (start, len(col_order))

    # Finals dedup (the Hyperscan shared-literal idiom): branches from
    # DIFFERENT rules that share (first segment, lead/real geometry,
    # anchor, suffix) are the SAME detection — allocate one conv column
    # and fan it out to every owning rule group in the b2g matmul. A
    # CRS-grade corpus (alternation products over shared token
    # vocabularies, paranoia-level near-duplicates) collapses ~10-40x
    # here; without it the conv pays one column per branch.
    final_chans: dict[tuple, list[int]] = {}
    final_gidsets: dict[tuple, list[set[int]]] = {}
    for gk, items in finals.items():
        uniq: dict[int, set[int]] = {}
        for bi, c in items:
            uniq.setdefault(c, set()).add(spec.branches[bi][0])
        final_chans[gk] = list(uniq)
        final_gidsets[gk] = [uniq[c] for c in final_chans[gk]]
    # Finals of ONE structure are batched over their suffixes: a site's
    # feed of thousands of rules is a few patterns with other tokens, so
    # every rule brings a suffix of its own (its second token) under a
    # handful of structures, and one AND-any per distinct suffix would
    # be thousands of one-column slices and conds in the program (PR 37:
    # 3,500 of them made the canary matcher 328k HLO lines and six
    # minutes of compile). A suffix's finals share a bucket when first
    # segment geometry, anchor and column count agree; the members of a
    # structure are ordered by the buckets they feed, so a bucket reads
    # contiguous runs of the structure's suffix bitmap and of m_all.
    sid_buckets: dict[int, list[tuple]] = {}
    for gk in finals:
        sid_buckets.setdefault(gk[0], []).append(gk[1:] + (len(final_chans[gk]),))
    final_runs: list[tuple] = []  # (sig_key, i0, i1, bucket, finals keys)
    for sig_key, members in struct.items():
        members.sort(key=lambda m: tuple(sorted(sid_buckets.get(m[1], ()))))
        seen_buckets: dict[tuple, list[int]] = {}
        for i, (_skey, sid) in enumerate(members):
            for bk in sid_buckets.get(sid, ()):
                seen_buckets.setdefault(bk, []).append(i)
        for bk, at in seen_buckets.items():
            i0 = at[0]
            for a, b in zip(at, at[1:] + [None]):
                if b != a + 1:
                    gks = [(members[i][1],) + bk[:3] for i in range(i0, a + 1)]
                    final_runs.append((sig_key, i0, a + 1, bk, gks))
                    i0 = b
    # A run of ns suffixes with k columns each is laid out block-major
    # (column j of every suffix side by side: k AND-anys over [T, Q, ns])
    # unless it is one suffix, or has more columns than suffixes (the
    # CRS shape: one shared suffix under a vocabulary of first tokens),
    # where every suffix keeps its own slice and AND-any.
    final_alloc: dict[tuple, tuple[int, int]] = {}
    run_alloc: list[tuple[int, int] | None] = []
    for _sig_key, i0, i1, bk, gks in final_runs:
        ns, k = i1 - i0, bk[3]
        if ns == 1 or k > ns:
            for gk in gks:
                final_alloc[gk] = alloc(final_chans[gk])
            run_alloc.append(None)
        else:
            run_alloc.append(alloc([final_chans[gk][j] for j in range(k) for gk in gks]))
    struct_alloc: dict[tuple, list[tuple[int, int]]] = {}
    for sig_key, members in struct.items():
        chan_cols = [
            [el[1] for el in skey[0] if el[0] == "seg"] for skey, _ in members
        ]
        n_slots = len(chan_cols[0]) if chan_cols else 0
        struct_alloc[sig_key] = [
            alloc([cc[slot] for cc in chan_cols]) for slot in range(n_slots)
        ]
    bucket_alloc: dict[tuple, list[tuple[int, int]]] = {}
    for sig_key, idxs in buckets.items():
        chan_lists = [
            [el[1] for el in spec.branches[bi][1] if el[0] == "seg"]
            for bi in idxs
        ]
        n_slots = len(chan_lists[0]) if chan_lists else 0
        bucket_alloc[sig_key] = [
            alloc([cl[slot] for cl in chan_lists]) for slot in range(n_slots)
        ]
    if not col_order:
        col_order = [0]

    # 2. conv: all segments, all start positions. out[t, p, n] == 2W ⇔
    # segment n matches the window starting at padded position p, with
    # ``128 // C`` taps a contraction (module docstring). The other end was
    # tried first: a full im2col matmul "was measured 1.6x SLOWER here at
    # XLA level" (commit 88b9050; the record names no shape and no chip):
    # all W·C channels a position, a [T·Q, W·C] window whose HBM traffic
    # exceeded the conv's MXU inefficiency. A fused Pallas finals tier
    # that built the windows in VMEM read 11.1–11.6 ms/step against
    # the plain conv's 6.9 on a v5e and was removed in PR 30; git history
    # has ops/segment_pallas.py. In the feed's ``32x2048`` matcher on a v5e
    # this form's block-0 conv reads 8.6 ms against 23.0 with one tap a
    # contraction, the launch 37.8 against 55.9; space-to-depth (no
    # dilation, k phase kernels, the output reshaped back) 87.8
    # (PERF.md §6, PR 47).
    with jax.named_scope("cko.seg.conv"):
        kernel_p = kernel[:, :, np.asarray(col_order)]  # [W, C, N2] tiny gather
        out = _conv_taps(spec, embed, kernel_p)  # [T, Q, N2]
        m_all = out >= jnp.bfloat16(2.0 * w)  # equality; >= is safe (2W is the max)

    def mslice(a0: int, a1: int) -> jnp.ndarray:
        """Columns [a0, a1) of the global allocation."""
        return m_all[:, :, a0:a1]

    with jax.named_scope("cko.seg.embed"):
        iota = jnp.arange(q, dtype=jnp.int32)[None, :]  # [1, Q]
        len1 = 1 + lengths[:, None]  # [T, 1] position just past the last byte
        iota3 = iota[..., None]  # [1, Q, 1]
        len3 = len1[..., None]  # [T, 1, 1]

    # Gap-class tables are built eagerly OUTSIDE the cond-gated chains:
    # tracers created inside one cond branch must not be cached and reused
    # inside another trace. NCE[t, p] (count of non-class bytes before p)
    # is monotone, so "NCE[p'] == NCE[p]" says the bytes [p, p') are all
    # in the class: the reachability test of every class gap. The block's
    # distinct classes are stacked and counted in one call.
    classes = list(dict.fromkeys(
        el[1] for _, prog, _, _ in spec.branches for el in prog if el[0] == "gapcls"
    ))
    nce_of: dict[tuple, jnp.ndarray] = {}
    if classes:
        with jax.named_scope("cko.seg.embed"):
            outside = [~_in_class(ivs, dpad)[:, :q] for ivs in classes]  # byte at p ∉ class
        with jax.named_scope("cko.seg.nce"):
            # Stacked along the rows and split after the count: with the
            # classes on an axis of their own from the start the TPU
            # compiler lays the channel planes above out for this matmul
            # and not for the conv (crs-lite 32x512 on a v5e: 266 relayout
            # copies and 7 unfused concatenates in cko.seg.embed, 3.2 ms
            # where it is 0.05).
            nces = _excl_prefix_count(jnp.concatenate(outside)).reshape(len(classes), t, q)
            nce_of = {ivs: nces[i] for i, ivs in enumerate(classes)}

    with jax.named_scope("cko.seg.embed"):
        big = jnp.int32(1 << 20)

    # The reachability tables of the classes whose unbounded gaps a WIDE
    # suffix structure crosses (``_reach_classes``): one set a class, out
    # here with the counts they are made of. A block whose structures are
    # all narrow builds none and traces as it always did.
    reach_of: dict[tuple, tuple] = {}
    for (sig_ops, _a_end), members in struct.items():
        for ivs in _reach_classes(sig_ops, t * q * len(members)):
            if ivs not in reach_of:
                with jax.named_scope("cko.seg.nce"):
                    reach_of[ivs] = _reach_tables(nce_of[ivs], big)

    def gap_cls(x: jnp.ndarray, ivs: tuple, lo: int, hi: int, forward: bool):
        """Class-gap op along axis 1 of [T, Q, NB]. Forward (suffix/RTL):
        out[p] = ∃d ∈ [lo, hi]: bytes [p, p+d) ∈ C ∧ x[p+d]. Backward
        (bucket/LTR): out[p'] = ∃d: bytes [p'-d, p') ∈ C ∧ x[p'-d].
        Unbounded gaps use the NCE latch (monotone non-class counts) as a
        log-shift running min — lax.cummax/cummin lower to reduce-window
        on TPU, which profiled at ~1/4 of this block's runtime — except
        forward over a block of ``_REACH_MIN_ELEMS`` elements or more, where
        the same bits come from ``_reach_gap``'s matmuls (module docstring)."""
        nce3 = nce_of[ivs][..., None]

        def clean(d: int) -> jnp.ndarray:
            if d == 0:
                return jnp.ones((t, q, 1), dtype=bool)
            return (
                jnp.pad(nce3, ((0, 0), (0, d), (0, 0)), constant_values=big)[:, d:]
                - nce3
            ) == 0

        if hi >= 0:
            if hi - lo + 1 <= 8:
                # Narrow window: shift-unrolled ORs beat the log passes.
                acc = jnp.zeros_like(x)
                for d in range(lo, hi + 1):
                    if forward:
                        acc = acc | (_lshift3(x, d) & clean(d))
                    else:
                        acc = acc | _rshift3(x & clean(d), d)
                return acc
            # Wide bounded window (CRS-grade .{0,60} class gaps): the
            # clean-span test "NCE[p'] == NCE[p]" (NCE is non-decreasing,
            # so candidates can never dip below) bounded to the window
            # [p+lo, p+hi] via an O(log span) windowed min — exact, and
            # ~span/log(span) fewer passes than the unrolled form.
            if forward:
                m = _window_min(jnp.where(x, nce3, big), lo, hi, big, forward=True)
                return m == nce3
            m = -_window_min(jnp.where(x, -nce3, big), lo, hi, big, forward=False)
            return m == nce3
        if forward:
            x1 = _lshift3(x, lo) & clean(lo) if lo else x
            if x.size >= _REACH_MIN_ELEMS:
                with jax.named_scope(_REACH_SCOPE):
                    return _reach_gap(x1, reach_of[ivs])
            h = _latch_min(jnp.where(x1, nce3, big), big, forward=True)
            return h == nce3
        x1 = _rshift3(x & clean(lo), lo) if lo else x
        h = -_latch_min(jnp.where(x1, -nce3, big), big, forward=False)
        return h == nce3

    def run_bucket(sig: tuple, idxs: list[int]) -> jnp.ndarray:
        ops, a_start, a_end = sig
        slots = bucket_alloc[sig]
        nb = len(idxs)

        # Single-seg unanchored fast path: evaluate at window starts, no
        # shifts at all (start/end constraints as comparisons on j).
        if len(ops) == 1 and ops[0][0] == "seg":
            _, n_lead, n_real = ops[0]
            a0, a1 = slots[0]
            m = mslice(a0, a1)  # [T, Q, NB]
            r = iota3 + n_lead  # real start for window at j
            ok = (r >= 1) & (r + n_real <= len3)
            if a_start:
                ok = ok & (r == 1)
            if a_end:
                ok = ok & (r + n_real == len3)
            return jnp.any(m & ok, axis=1)  # [T, NB]

        def run_chain(_):
            e = (iota3 == 1) if a_start else (iota3 >= 1)
            e = jnp.broadcast_to(e, (t, q, nb))
            seg_i = 0
            for op in ops:
                if op[0] == "seg":
                    _, n_lead, n_real = op
                    a0, a1 = slots[seg_i]
                    seg_i += 1
                    m = mslice(a0, a1)  # [T, Q, NB]
                    if n_lead:
                        m = jnp.pad(m, ((0, 0), (n_lead, 0), (0, 0)))[:, :q]
                    valid = (iota3 >= 1) & (iota3 + n_real <= len3)
                    e = e & m & valid
                    if n_real:
                        e = jnp.pad(e, ((0, 0), (n_real, 0), (0, 0)))[:, :q]
                elif op[0] == "gapany":
                    # e_out[p] = ∃d ∈ [lo, hi]: e[p - d] — log-shift OR.
                    _, lo, hi = op
                    e = _spread_or(e, lo, hi, forward=False)
                else:  # gapcls
                    _, ivs, lo, hi = op
                    e = gap_cls(e, ivs, lo, hi, forward=False)
            if a_end:
                return jnp.any(e & (iota3 == len3), axis=1)
            return jnp.any(e & (iota3 <= len3), axis=1)

        # Prefilter gate (the Hyperscan idea as lax.cond): if this bucket's
        # first segments match NOWHERE in the whole block, no row can match
        # any of its branches — skip the chain entirely. Worst case is
        # unchanged; benign-heavy traffic skips almost every chain.
        if slots:
            a0, a1 = slots[0]
            pred = jnp.any(mslice(a0, a1))
            # The no-match branch derives its zeros from m_all so both
            # branches carry the same varying-axes type under shard_map.
            no_match = jnp.broadcast_to(m_all[:, 0, :1] & False, (t, nb))
            return jax.lax.cond(pred, run_chain, lambda _: no_match, None)
        return run_chain(None)

    # --- suffix-deduped tier (a) ---
    # Right-to-left evaluation, batched over the group's distinct
    # suffixes: s[t, p, i] = "suffix i fully matches with its first
    # element's real bytes starting at padded position p".
    s_struct: dict[tuple, jnp.ndarray] = {}
    for si, (sig_key, members) in enumerate(struct.items()):
        with jax.named_scope("cko.seg.suffix"), jax.named_scope(f"b{block_index}.st{si:03d}"):
            sig_ops, a_end = sig_key
            ns = len(members)
            # Base: "the element AFTER the suffix may start at p" — one past
            # the last byte for $-anchored branches, anywhere in range else.
            s = jnp.broadcast_to(
                (iota3 == len3) if a_end else (iota3 <= len3), (t, q, ns)
            )
            seg_slot = sum(1 for o in sig_ops if o[0] == "seg")
            for op in reversed(sig_ops):
                if op[0] == "seg":
                    seg_slot -= 1
                    _, n_lead, n_real = op
                    a0, a1 = struct_alloc[sig_key][seg_slot]
                    m = mslice(a0, a1)  # [T, Q, NS] at window starts
                    if n_lead:
                        m = _rshift3(m, n_lead)  # index by real start
                    valid = (iota3 >= 1) & (iota3 + n_real <= len3)
                    s = m & valid & _lshift3(s, n_real)
                elif op[0] == "gapany":
                    # s_k[p] = ∃d ∈ [lo, hi]: s[p + d] — log-shift OR spread.
                    _, lo, hi = op
                    s = _spread_or(s, lo, hi, forward=True)
                else:  # gapcls
                    _, ivs, lo, hi = op
                    s = gap_cls(s, ivs, lo, hi, forward=True)
            s_struct[sig_key] = s

    # Concatenate bucket outputs (bucket order) and map columns to groups
    # with one matmul — no scatter (TPU scatter lowering serializes).
    with jax.named_scope("cko.seg.fold"):
        hits = jnp.zeros((t, spec.n_groups), dtype=bool)
    if spec.branches:
        cols: list[jnp.ndarray] = []
        col_groups: list[int] = []
        for sig, idxs in buckets.items():
            with jax.named_scope("cko.seg.bucket"):
                cols.append(run_bucket(sig, idxs))  # [T, len(idxs)]
            col_groups.extend(spec.branches[bi][0] for bi in idxs)
        iota2 = iota  # [1, Q]

        def final_cols(a0: int, a1: int, gate: jnp.ndarray) -> jnp.ndarray:
            """AND-any of conv columns ``a0:a1`` ([T, Q, NB]) under
            ``gate`` ([T, Q, 1] or [T, Q, NB]) -> [T, NB].

            Prefilter gate (as in the bucketed tier): if none of these
            first segments matched anywhere in the block, skip the
            AND-any reduction entirely — benign-heavy traffic pays only
            the cheap any() read. ONLY for small column groups: the
            any() itself is a full read of the slice, and a
            many-hundred-column group in a serving-sized batch almost
            always has some hit somewhere, so the gate would pay a
            whole extra [T, Q, NB] pass (profiled at ~1.1 ms/step as
            fusion.406) to skip nothing."""
            m = mslice(a0, a1)

            def run_final(_):
                return jnp.any(m & gate, axis=1)  # [T, NB]

            if a1 - a0 > 64:
                return run_final(None)
            no_match = jnp.broadcast_to(m_all[:, 0, :1] & False, (t, a1 - a0))
            return jax.lax.cond(jnp.any(m), run_final, lambda _: no_match, None)

        with jax.named_scope("cko.seg.final"):
            for (sig_key, i0, i1, bk, gks), block in zip(final_runs, run_alloc):
                n_lead, n_real, a_start, k = bk
                if block is None:
                    for i, gk in zip(range(i0, i1), gks):
                        # [T, Q], indexed by real start of the NEXT element
                        s2 = s_struct[sig_key][:, :, i]
                        g = (
                            (iota2 >= 1)
                            & (iota2 + n_real <= len1)
                            & _lshift_fill(s2, n_real, False)
                        )
                        if a_start:
                            g = g & (iota2 == 1)
                        gj = _lshift_fill(g, n_lead, False)  # window-start idx
                        cols.append(final_cols(*final_alloc[gk], gj[:, :, None]))
                        col_groups.extend(final_gidsets[gk])  # deduped: one col → gid set
                    continue
                ns = i1 - i0
                g3 = (
                    (iota3 >= 1)
                    & (iota3 + n_real <= len3)
                    & _lshift3(s_struct[sig_key][:, :, i0:i1], n_real)
                )
                if a_start:
                    g3 = g3 & (iota3 == 1)
                gj3 = _lshift3(g3, n_lead)  # [T, Q, ns], window-start idx
                for j in range(k):
                    a0 = block[0] + j * ns
                    cols.append(final_cols(a0, a0 + ns, gj3))
                    col_groups.extend(final_gidsets[gk][j] for gk in gks)
        with jax.named_scope("cko.seg.fold"):
            bh_all = jnp.concatenate(cols, axis=1)
            b2g = np.zeros((len(col_groups), spec.n_groups), dtype=np.float32)
            for ci, gid in enumerate(col_groups):
                if isinstance(gid, set):
                    for g in gid:
                        b2g[ci, g] = 1
                else:
                    b2g[ci, gid] = 1
            # bf16 matmul (exact: sums <= branches-per-group << 256); int8
            # DotGeneral lowers off the MXU on TPU.
            hits = (
                jnp.dot(
                    bh_all.astype(jnp.bfloat16),
                    jnp.asarray(b2g, dtype=jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
                > 0
            )
    if spec.always:
        al = np.zeros(spec.n_groups, dtype=bool)
        for gid in spec.always:
            al[gid] = True
        with jax.named_scope("cko.seg.fold"):
            hits = hits | jnp.asarray(al)[None, :]
    return hits
