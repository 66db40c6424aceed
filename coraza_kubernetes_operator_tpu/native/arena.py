"""Staging arena: page-aligned, reusable host slabs for tiered export.

Every blob window used to allocate nine fresh numpy arrays a tier
(`np.zeros` churn in ``native/__init__.py::_export``) and then re-slice
them into tier buffers in Python. The arena replaces that with a pool of
buffer SETS keyed by the window's quantized shape signature — the
``_bucket``/``_bucket_rows`` lattice keeps the key space tiny, so
steady-state serving recycles the same few sets forever. C++
(``cko_plan_export``) writes real rows and zeroes ONLY the pad regions
it does not write, so a dirty reused buffer is indistinguishable from a
fresh ``np.zeros`` one.

A set is the window as the device takes it (``models/slab.py`` is the
one layout): **one match slab a tier** — ``uint8 [1 + H + E, U, L]``:
``data``, the ``H`` planes of ``vdata``, then ``lengths`` and
``vlengths`` as little-endian ``int32`` — and **one post slab a
window** — ``int32 [words]``: every tier's ``k1, k2, k3, req_id, uid``,
``numvals`` and, where the value cache is on, every tier's ``cached``
block. A launch hands the device the slab, one transfer; the nine
per-tier arrays, ``numvals`` and ``cached`` that the native export, the
prefilter confirm, the host twins and the value cache read and write are
NumPy views into the slabs at the layout's offsets. Each slab starts on
a page; bytes no view covers are zero from allocation.

Recycling discipline: a set is checked out under an ``ArenaLease`` and
must not return to the pool until the window's device step has consumed
the host slabs — ``WafEngine.collect`` releases the lease after
``device_get`` (execution done implies inputs consumed: every slab is
a launch's own operand; the CPU backend may alias
suitably-aligned numpy buffers zero-copy, which is exactly why these
slabs are page-aligned AND why early recycling would corrupt an
in-flight window). A lease that is never released (abandoned window)
just leaks one buffer set — the pool reallocates on the next miss.

The arena lives on the ``NativeTensorizer`` — one per engine — so an
engine hot-swap gets a fresh arena and buffers from the old engine can
never serve windows of the new one. A window the Python tensorizer
built has no arena: ``stage_window`` copies it into a set of its own.

``CKO_STAGING_ARENA_MAX`` bounds retained sets across all signatures
(default 64; 0 keeps the arena transient: every checkout allocates and
every release drops).
"""

from __future__ import annotations

import os
import threading

import numpy as np

from ..models.slab import (
    match_slab_shape,
    match_views,
    post_layout,
    post_slab_words,
    post_views,
)

_PAGE = 4096


def _aligned(shape, dtype):
    """A page-aligned, zeroed numpy array (XLA's CPU client can then
    borrow the buffer zero-copy instead of re-staging it)."""
    dt = np.dtype(dtype)
    nbytes = int(np.prod(shape)) * dt.itemsize
    raw = np.zeros(nbytes + _PAGE, dtype=np.uint8)
    off = (-raw.ctypes.data) % _PAGE
    return raw[off : off + nbytes].view(dt).reshape(shape)


def _alloc(signature: tuple) -> tuple:
    """One buffer set for ``signature = (((U, L, P, Uc), ...per tier),
    H, B, NV, PB)`` (``Uc`` rows of ``PB`` bytes of cached hit rows a
    tier; 0 and 0 with the value cache off): ``(tiers, numvals, cached,
    match_slabs, post_slab)``, the first three views into the last two."""
    tier_shapes, h, b, nv, pb = signature
    match_slabs = tuple(
        _aligned(match_slab_shape(u, length, h), np.uint8)
        for u, length, _p, _uc in tier_shapes
    )
    layout = (tuple((p, uc) for _u, _l, p, uc in tier_shapes), b, nv, pb)
    post_slab = _aligned((post_slab_words(layout),), np.int32)
    pairs, numvals, cached = post_views(post_slab, layout)
    tiers = []
    for slab, (k1, k2, k3, req_id, uid) in zip(match_slabs, pairs):
        data, lengths, vdata, vlengths = match_views(slab)
        tiers.append((data, lengths, k1, k2, k3, req_id, vdata, vlengths, uid))
    return tuple(tiers), numvals, cached, match_slabs, post_slab


class ArenaLease:
    """One checked-out buffer set, the window as a launch takes it:
    ``match_slabs`` (one ``uint8`` slab a tier) and ``post_slab``
    (``int32``) go to the device; ``tiers`` — a list of 9-tuples (data,
    lengths, k1, k2, k3, req_id, vdata, vlengths, uid) —, ``numvals``
    (the per-request numeric matrix) and ``cached`` (per tier the
    bit-packed cached hit rows, or None with the value cache off) are
    the views into them that the host reads and writes
    (``models/slab.py``). ``release()`` returns the set to the pool
    (idempotent — double release is a no-op, never a double-insert; a
    ``stage_window`` set has no pool and just drops)."""

    __slots__ = (
        "tiers", "numvals", "cached", "match_slabs", "post_slab",
        "_arena", "_key", "_set", "_released",
    )

    def __init__(self, arena, key, bufset):
        self._arena = arena
        self._key = key
        self._set = bufset
        self._released = False
        self.tiers, self.numvals, self.cached, self.match_slabs, self.post_slab = bufset

    def release(self) -> None:
        if self._released or self._arena is None:
            return
        self._released = True
        self._arena._put_back(self._key, self._set)


def stage_window(tiers, numvals, cached) -> ArenaLease:
    """Lay a window out in slabs of its own: what a launch needs of a
    window that no arena staged (the Python tensorizer's, ``tier_tensors``'
    output). The operands are copied into the views of a fresh set, once."""
    dims, b, nv, pb = post_layout(tiers, numvals, cached)
    signature = (
        tuple(t[0].shape + dim for t, dim in zip(tiers, dims)),
        tiers[0][6].shape[0], b, nv, pb,
    )
    lease = ArenaLease(None, signature, _alloc(signature))
    for src, dst in zip(tiers, lease.tiers):
        for a, view in zip(src, dst):
            np.copyto(view, a, casting="no")
    np.copyto(lease.numvals, numvals, casting="no")
    if cached is not None:
        for a, view in zip(cached, lease.cached):
            if a is not None:
                np.copyto(view, a, casting="no")
    return lease


class StagingArena:
    """Thread-safe pool of tier-shaped staging buffer sets."""

    def __init__(self, max_sets: int | None = None):
        if max_sets is None:
            max_sets = int(os.environ.get("CKO_STAGING_ARENA_MAX", "64"))
        self.max_sets = max_sets
        self._pool: dict[tuple, list] = {}
        self._lock = threading.Lock()
        self._retained = 0
        self.reuses_total = 0
        self.allocs_total = 0

    def checkout(self, signature: tuple) -> ArenaLease:
        """signature = (((U, L, P, Uc), ...per tier), H, B, NV, PB)."""
        with self._lock:
            sets = self._pool.get(signature)
            if sets:
                bufset = sets.pop()
                self._retained -= 1
                self.reuses_total += 1
                return ArenaLease(self, signature, bufset)
            self.allocs_total += 1
        return ArenaLease(self, signature, _alloc(signature))

    def _put_back(self, signature: tuple, bufset) -> None:
        with self._lock:
            if self._retained >= self.max_sets:
                return  # transient: drop, the GC reclaims it
            self._pool.setdefault(signature, []).append(bufset)
            self._retained += 1

    def stats(self) -> dict:
        with self._lock:
            return {
                "buffers": self._retained,
                "reuses_total": self.reuses_total,
                "allocs_total": self.allocs_total,
            }
