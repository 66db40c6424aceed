"""Tiered native window pipeline + staging arena (docs/NATIVE.md).

Differential contract: ``NativeTensorizer.tier_blob`` (two GIL-released
C++ calls scattering into arena buffers) must be bit-identical to the
Python reference (``blob_requests`` -> extract -> ``_tensorize`` ->
``tier_tensors``) — tiers, numvals, masks, cached rows, miss keys —
with the value cache cold AND warm. Plus the arena lifecycle
invariants: zero-copy blob handoff, same-shape reuse allocates nothing,
pad regions are re-zeroed on dirty reuse, concurrent leases never
share buffers, hot-swapped engines never share an arena.

The library is ``conftest.py``'s ``native_lib``, loaded for this module
alone (``native_loaded``).
"""

import ctypes

import numpy as np
import pytest

from coraza_kubernetes_operator_tpu.engine import WafEngine
from coraza_kubernetes_operator_tpu.engine.waf import tier_tensors
from coraza_kubernetes_operator_tpu.native import (
    blob_requests,
    serialize_requests,
)
from coraza_kubernetes_operator_tpu.native.arena import StagingArena

from test_native import RULES, _random_requests

pytestmark = pytest.mark.usefixtures("native_loaded")


@pytest.fixture(scope="module")
def engine(native_loaded):
    eng = WafEngine(RULES)
    assert eng._native.tiered
    return eng


_TIER_NAMES = (
    "data", "lengths", "k1", "k2", "k3", "req_id", "vdata", "vlengths", "uid",
)


def _python_reference(engine, blob, n, cache):
    """The pure-Python window pipeline on the same blob + cache state."""
    reqs = blob_requests(blob, n)
    extractions = [engine.extractor.extract(r) for r in reqs]
    tensors = engine._tensorize(extractions)
    if cache is None:
        tiers, numvals, masks = tier_tensors(tensors, engine._kind_block_lut)
        return tiers, numvals, masks, None, None
    return tier_tensors(tensors, engine._kind_block_lut, cache=cache)


def _assert_window_parity(engine, reqs, cache, tag):
    blob = serialize_requests(reqs)
    n = len(reqs)
    p_tiers, p_numvals, p_masks, p_cached, p_miss = _python_reference(
        engine, blob, n, cache
    )
    t_tiers, t_numvals, t_masks, t_cached, t_miss, lease = (
        engine._native.tier_blob(blob, n, engine._kind_block_lut, cache)
    )
    try:
        assert t_masks == p_masks, tag
        assert len(t_tiers) == len(p_tiers), tag
        for ti, (tt, pt) in enumerate(zip(t_tiers, p_tiers)):
            for name, x, y in zip(_TIER_NAMES, tt, pt):
                x, y = np.asarray(x), np.asarray(y)
                assert x.shape == y.shape and x.dtype == y.dtype, (
                    tag, ti, name, x.shape, y.shape
                )
                assert (x == y).all(), (
                    tag, ti, name, np.argwhere(x != y)[:5]
                )
        assert (np.asarray(t_numvals) == np.asarray(p_numvals)).all(), tag
        if cache is not None:
            for ti, (tc, pc) in enumerate(zip(t_cached, p_cached)):
                assert (np.asarray(tc) == np.asarray(pc)).all(), (tag, ti)
            assert t_miss == p_miss, tag
    finally:
        lease.release()


def test_tiered_parity_no_cache(engine):
    for seed in (1, 2, 3):
        _assert_window_parity(
            engine, _random_requests(64, seed), None, f"seed{seed}"
        )


def test_tiered_parity_tiny_windows(engine):
    # Non-power-of-two counts exercise pad rows in every tier.
    for n in (1, 2, 3, 5):
        _assert_window_parity(
            engine, _random_requests(n, 100 + n), None, f"n{n}"
        )


def test_tiered_parity_cache_cold_and_warm(engine):
    cache = engine.value_cache
    assert cache is not None
    reqs = _random_requests(64, 9)
    # Cold probe: everything misses.
    _assert_window_parity(engine, reqs, cache, "cold")
    # Warm the cache through the full serving path (collect inserts the
    # matcher's hit rows), then re-probe the SAME window: the found/miss
    # remap (found rows land at u_pad + rank) must agree bit-for-bit.
    blob = serialize_requests(reqs)
    engine.collect(engine.prepare_blob(blob, len(reqs)))
    _assert_window_parity(engine, reqs, cache, "warm")
    # Mixed: half repeated (cache hits), half fresh (misses).
    mixed = reqs[:32] + _random_requests(32, 10)
    _assert_window_parity(engine, mixed, cache, "mixed")


def test_tiered_verdict_parity(engine):
    reqs = _random_requests(96, 21)
    blob = serialize_requests(reqs)
    tiered = engine.collect(engine.prepare_blob(blob, len(reqs)))
    python = engine.collect(engine.prepare(blob_requests(blob, len(reqs))))
    for i, (a, b) in enumerate(zip(tiered, python)):
        assert (a.interrupted, a.status, a.rule_id, a.matched_ids) == (
            b.interrupted, b.status, b.rule_id, b.matched_ids
        ), (i, reqs[i].uri)


# -- zero-copy blob handoff ---------------------------------------------------


class _NoCopy(bytearray):
    """Trips on any ``bytes(blob)`` defensive copy: ``bytes()`` consults
    ``__bytes__`` before the buffer protocol, while ctypes
    ``from_buffer`` (the zero-copy path) never calls it."""

    def __bytes__(self):
        raise AssertionError("blob was copied via bytes() — zero-copy broken")


def test_blob_handoff_is_zero_copy(engine):
    reqs = _random_requests(16, 4)
    blob = serialize_requests(reqs)
    guarded = _NoCopy(blob)

    ref = engine._native.tensorize_blob(blob, len(reqs))
    got = engine._native.tensorize_blob(guarded, len(reqs))
    for a, b in zip(ref, got):
        assert (np.asarray(a) == np.asarray(b)).all()

    t_ref = engine._native.tier_blob(blob, len(reqs), engine._kind_block_lut)
    t_got = engine._native.tier_blob(guarded, len(reqs), engine._kind_block_lut)
    try:
        for tt, pt in zip(t_ref[0], t_got[0]):
            for a, b in zip(tt, pt):
                assert (np.asarray(a) == np.asarray(b)).all()
    finally:
        t_ref[5].release()
        t_got[5].release()


def test_prepare_blob_accepts_bytearray(engine):
    """The ingest frontend hands its window as a bytearray: the FULL
    prepare_blob path (incl. the blob_over_limit pre-pass, which once
    fed the raw bytearray to a c_void_p arg and ArgumentError'd the
    whole window into the host fallback) must serve it zero-copy."""
    reqs = _random_requests(24, 13)
    blob = serialize_requests(reqs)
    want = engine.collect(engine.prepare_blob(blob, len(reqs)))
    got = engine.collect(engine.prepare_blob(_NoCopy(blob), len(reqs)))
    assert [
        (v.interrupted, v.status, v.rule_id, v.matched_ids) for v in want
    ] == [(v.interrupted, v.status, v.rule_id, v.matched_ids) for v in got]


def test_blob_handoff_pins_buffer(engine):
    """While C++ reads the window, the bytearray's buffer is exported —
    a resize (which would invalidate the pointer mid-call) must raise."""
    from coraza_kubernetes_operator_tpu.native import _buf_arg

    blob = bytearray(serialize_requests(_random_requests(4, 5)))
    arr = _buf_arg(blob)
    assert ctypes.addressof(arr) == ctypes.addressof(
        (ctypes.c_ubyte * len(blob)).from_buffer(blob)
    )
    with pytest.raises(BufferError):
        blob.append(0)
    del arr
    blob.append(0)  # released: resizable again


# -- staging arena ------------------------------------------------------------

# (((U, L, P, Uc), ...per tier), H, B, NV, PB)
_SIG = (((8, 32, 16, 2), (4, 64, 8, 1)), 2, 8, 4, 5)


def test_arena_same_shape_reuse_allocates_nothing():
    arena = StagingArena(max_sets=8)
    lease = arena.checkout(_SIG)
    lease.release()
    assert arena.stats() == {
        "buffers": 1, "reuses_total": 0, "allocs_total": 1,
    }
    for _ in range(5):
        lease = arena.checkout(_SIG)
        lease.release()
    s = arena.stats()
    assert s["allocs_total"] == 1 and s["reuses_total"] == 5


def test_arena_reuse_through_tier_blob(engine):
    reqs = _random_requests(32, 6)
    blob = serialize_requests(reqs)
    arena = engine._native._arena
    out1 = engine._native.tier_blob(blob, len(reqs), engine._kind_block_lut)
    out1[5].release()
    allocs = arena.stats()["allocs_total"]
    reuses = arena.stats()["reuses_total"]
    out2 = engine._native.tier_blob(blob, len(reqs), engine._kind_block_lut)
    out2[5].release()
    s = arena.stats()
    assert s["allocs_total"] == allocs, "same-shape window must not allocate"
    assert s["reuses_total"] == reuses + 1


def test_arena_pad_rows_rezeroed_after_dirty_reuse(engine):
    """A recycled buffer full of garbage must export bit-identically to
    a fresh one: cko_plan_export zeroes every pad region it skips."""
    reqs = _random_requests(48, 8)
    blob = serialize_requests(reqs)
    tiers, numvals, *_rest, lease = engine._native.tier_blob(
        blob, len(reqs), engine._kind_block_lut
    )
    want_tiers = [[np.asarray(a).copy() for a in t] for t in tiers]
    want_numvals = np.asarray(numvals).copy()
    lease.release()
    # Poison the pooled buffers through the same array objects.
    for t in lease.tiers:
        for a in t:
            np.asarray(a)[...] = np.iinfo(a.dtype).max if a.dtype != np.uint8 else 0xAB
    np.asarray(lease.numvals)[...] = -1
    reuses = engine._native._arena.stats()["reuses_total"]
    tiers2, numvals2, *_rest2, lease2 = engine._native.tier_blob(
        blob, len(reqs), engine._kind_block_lut
    )
    try:
        assert engine._native._arena.stats()["reuses_total"] == reuses + 1
        for wt, t in zip(want_tiers, tiers2):
            for name, a, b in zip(_TIER_NAMES, wt, t):
                assert (a == np.asarray(b)).all(), (
                    name, np.argwhere(a != np.asarray(b))[:5]
                )
        assert (want_numvals == np.asarray(numvals2)).all()
    finally:
        lease2.release()


def test_arena_concurrent_leases_never_share_buffers():
    arena = StagingArena(max_sets=8)
    l1 = arena.checkout(_SIG)
    l2 = arena.checkout(_SIG)
    for t1, t2 in zip(l1.tiers, l2.tiers):
        for a, b in zip(t1, t2):
            assert a.ctypes.data != b.ctypes.data
    assert l1.numvals.ctypes.data != l2.numvals.ctypes.data
    l1.release()
    l2.release()
    # Recycled leases stay distinct too.
    l3 = arena.checkout(_SIG)
    l4 = arena.checkout(_SIG)
    assert l3.tiers[0][0].ctypes.data != l4.tiers[0][0].ctypes.data
    assert arena.stats()["reuses_total"] == 2


def _extent(a):
    """[first byte, one past the last) of a C-contiguous array."""
    assert a.flags["C_CONTIGUOUS"]
    return a.ctypes.data, a.ctypes.data + a.nbytes


def _assert_lies_in_slabs(lease):
    """Every view of the lease lies in one of its slabs, on a boundary
    of its item size, and no two views share a byte; each slab starts
    on a page."""
    slabs = [_extent(s) for s in (*lease.match_slabs, lease.post_slab)]
    for lo, _hi in slabs:
        assert lo % 4096 == 0
    views = [a for t in lease.tiers for a in t] + [lease.numvals]
    views += [c for c in lease.cached or () if c is not None]
    spans = []
    for a in views:
        lo, hi = _extent(a)
        assert lo % a.itemsize == 0
        assert any(s_lo <= lo and hi <= s_hi for s_lo, s_hi in slabs), a.shape
        if hi > lo:
            spans.append((lo, hi))
    spans.sort()
    for (_lo, hi), (lo, _hi) in zip(spans, spans[1:]):
        assert hi <= lo, "two views overlap"
    # The match operands lie in the tier's own slab, the pair rows,
    # numvals and the cached blocks in the window's one post slab.
    post_lo, post_hi = slabs[-1]
    for t, (m_lo, m_hi) in zip(lease.tiers, slabs):
        for k, a in enumerate(t):
            lo, hi = _extent(a)
            s_lo, s_hi = (m_lo, m_hi) if k in (0, 1, 6, 7) else (post_lo, post_hi)
            assert s_lo <= lo and hi <= s_hi, k
    # Post-slab fields start on a 64-byte line.
    for a in [a for t in lease.tiers for k, a in enumerate(t) if k in (2, 3, 4, 5, 8)]:
        assert a.ctypes.data % 64 == 0


def test_arena_views_lie_in_page_aligned_slabs():
    arena = StagingArena(max_sets=1)
    lease = arena.checkout(_SIG)
    assert len(lease.match_slabs) == 2 and lease.post_slab.dtype == np.int32
    for slab, (u, length, _p, _uc) in zip(lease.match_slabs, _SIG[0]):
        assert slab.dtype == np.uint8 and slab.shape[1:] == (u, length)
    assert [c.shape for c in lease.cached] == [(2, 5), (1, 5)]
    _assert_lies_in_slabs(lease)
    lease.release()
    # With the value cache off the post slab carries no cached block.
    off = arena.checkout((((8, 32, 16, 0),), 1, 8, 4, 0))
    assert off.cached is None
    _assert_lies_in_slabs(off)


@pytest.mark.parametrize("cache_on", [False, True])
def test_tier_blob_lease_lies_in_slabs(engine, cache_on):
    """A served window's nine views a tier, ``numvals`` and the cached
    blocks ARE the slabs a launch hands the device: what C++ exported
    through the views is what the slabs hold."""
    from coraza_kubernetes_operator_tpu.models.slab import match_views, post_views, post_layout

    reqs = _random_requests(48, 31)
    blob = serialize_requests(reqs)
    cache = engine.value_cache if cache_on else None
    tiers, numvals, _masks, cached, _miss, lease = engine._native.tier_blob(
        blob, len(reqs), engine._kind_block_lut, cache
    )
    try:
        assert tiers is lease.tiers and numvals is lease.numvals
        assert (cached is None) == (not cache_on) and cached is lease.cached
        _assert_lies_in_slabs(lease)
        pairs, nv, cpk = post_views(
            lease.post_slab, post_layout(tiers, numvals, cached)
        )
        assert (nv == numvals).all()
        for ti, (t, slab) in enumerate(zip(tiers, lease.match_slabs)):
            for got, want in zip(match_views(slab), (t[0], t[1], t[6], t[7])):
                assert got.shape == want.shape and (got == want).all()
            for got, want in zip(pairs[ti], (t[2], t[3], t[4], t[5], t[8])):
                assert got.ctypes.data == want.ctypes.data
            if cache_on:
                assert cpk[ti].ctypes.data == cached[ti].ctypes.data
    finally:
        lease.release()


def test_arena_transient_mode():
    """CKO_STAGING_ARENA_MAX=0 semantics: nothing retained, every
    checkout allocates."""
    arena = StagingArena(max_sets=0)
    arena.checkout(_SIG).release()
    arena.checkout(_SIG).release()
    assert arena.stats() == {
        "buffers": 0, "reuses_total": 0, "allocs_total": 2,
    }


def test_arena_release_idempotent():
    arena = StagingArena(max_sets=8)
    lease = arena.checkout(_SIG)
    lease.release()
    lease.release()  # no double-insert
    assert arena.stats()["buffers"] == 1
    l1 = arena.checkout(_SIG)
    l2 = arena.checkout(_SIG)  # pool must NOT hand out the same set twice
    assert l1.tiers[0][0].ctypes.data != l2.tiers[0][0].ctypes.data


def test_arena_hot_swap_isolation():
    """Each engine owns its arena: a hot swap can never serve a new
    engine's window from the old engine's live buffers."""
    e1 = WafEngine(RULES)
    e2 = WafEngine(RULES)
    assert e1._native._arena is not e2._native._arena
    l1 = e1._native._arena.checkout(_SIG)
    l2 = e2._native._arena.checkout(_SIG)
    assert l1.tiers[0][0].ctypes.data != l2.tiers[0][0].ctypes.data
    l1.release()
    l2.release()
    assert e2._native._arena.stats()["buffers"] == 1
    assert e1._native._arena.stats()["buffers"] == 1


def test_native_stats_shape(engine):
    s = engine.native_stats()
    assert s["available"] and s["tiered"]
    assert s["windows_total"] >= 1
    assert s["window_s_total"] > 0.0
    arena = s["arena"]
    assert arena["reuses_total"] + arena["allocs_total"] >= 1
    assert set(arena) == {"buffers", "reuses_total", "allocs_total"}
