"""Window slabs: the one layout of what a launch hands the device.

A host array handed to an executable is one host-to-device transfer,
and a window's cost there is the COUNT of its operands, not their bytes
(a ``32x512`` tier is 16 KB). So a tier's matcher takes one **match
slab** and the window's post stage one **post slab**; the nine per-tier
arrays and ``numvals`` every host reader knows are views into them, and
the two served executables (``models/waf_model.py``:
``match_tier_packed``, ``eval_post_tiered``) recover the same arrays on
the device with static slices. This module is the only place that knows
where a field lies: the staging arena allocates from it
(``native/arena.py``), ``stage_window`` there copies a Python-tensorized
window into it, ``WafEngine._tier_specs`` shapes the placeholders the
executables are compiled with from it, and the executables unpack by it.

**Match slab** of a tier of ``U`` unique rows x width ``L`` with ``H``
host-variant slots: ``uint8 [1 + H + E, U, L]``. Plane 0 is ``data
[U, L]``, planes ``1 .. H`` are ``vdata [H, U, L]``, and the last ``E =
ceil(4 (1 + H) / L)`` planes begin with ``(1 + H) * U`` little-endian
``int32``: ``lengths [U]`` then ``vlengths [H, U]``. ``U`` and ``L`` are
the slab's own shape and ``H`` follows from its plane count, so the
shape alone says where everything is.

**Post slab** of a window: ``int32 [words]``, fields in this order, each
starting on a 64-byte line: per tier ``k1, k2, k3, req_id, uid [P]``;
``numvals [B, NV]``; then, where the value cache is on, per tier the
``cached [Uc, PB] uint8`` block, four bytes a word. Its offsets are a
static function of ``(((P, Uc), ...), B, NV, PB)``: the ``layout`` the
post executable takes as a static argument.

Bytes no field covers (the tail of the length planes, the gaps between
post fields) are zero from allocation and never written.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

# int32 words: every field of a post slab starts on a 64-byte line.
_FIELD_ALIGN = 16


def match_slab_shape(u: int, length: int, h: int) -> tuple[int, int, int]:
    """Shape of the ``uint8`` match slab of a ``U x L`` tier with ``h``
    host-variant slots."""
    return (1 + h + -(-4 * (1 + h) // length), u, length)


def _match_slab_h(planes: int, length: int) -> int:
    """``h`` of a match slab with that many planes (the plane count grows
    strictly with ``h``, so it is unique)."""
    for h in range(1, planes):
        if match_slab_shape(1, length, h)[0] == planes:
            return h
    raise ValueError(f"no match slab has {planes} planes of width {length}")


def _split_match(slab, as_words):
    planes, u, length = slab.shape
    h = _match_slab_h(planes, length)
    n = 4 * (1 + h) * u  # bytes of lengths + vlengths
    rows = slab[1 + h :].reshape(-1, length)[: -(-n // length)]
    lens = as_words(rows.reshape(-1)[:n]).reshape(1 + h, u)
    return slab[0], lens[0], slab[1 : 1 + h], lens[1:]


def match_views(slab: np.ndarray):
    """``(data, lengths, vdata, vlengths)``: NumPy views into a match
    slab (host side: the native export's pointers, the confirm, the host
    twins)."""
    return _split_match(slab, lambda b: b.view(np.int32))


def unpack_match_slab(slab):
    """The same four operands from the slab on the device (inside
    ``match_tier_packed``)."""
    return _split_match(
        slab, lambda b: lax.bitcast_convert_type(b.reshape(-1, 4), jnp.int32)
    )


def post_layout(tiers, numvals, cached) -> tuple:
    """The static ``layout`` of a window's post slab, from the window's
    own operands (shapes only): ``(((P, Uc), ...), B, NV, PB)`` with
    ``Uc`` 0 where a tier carries no cached block and ``PB`` 0 where
    none does."""
    blocks = [None] * len(tiers) if cached is None else cached
    dims = tuple(
        (int(t[2].shape[0]), 0 if c is None else int(c.shape[0]))
        for t, c in zip(tiers, blocks)
    )
    pb = max((int(c.shape[1]) for c in blocks if c is not None), default=0)
    b, nv = numvals.shape
    return dims, int(b), int(nv), pb


def _post_spans(layout) -> tuple[list[tuple[int, int]], int]:
    """Word ``(offset, length)`` of every field in slab order, and the
    slab's length in words."""
    dims, b, nv, pb = layout
    sizes = [p for p, _uc in dims for _ in range(5)]
    sizes.append(b * nv)
    sizes += [-(-uc * pb // 4) for _p, uc in dims]
    spans, off = [], 0
    for n in sizes:
        spans.append((off, n))
        off += -(-n // _FIELD_ALIGN) * _FIELD_ALIGN
    return spans, off


def post_slab_words(layout) -> int:
    return _post_spans(layout)[1]


def _split_post(slab, layout, as_bytes):
    dims, b, nv, pb = layout
    spans, _words = _post_spans(layout)
    fields = [slab[off : off + n] for off, n in spans]
    nt = len(dims)
    pairs = tuple(tuple(fields[5 * ti : 5 * ti + 5]) for ti in range(nt))
    numvals = fields[5 * nt].reshape(b, nv)
    cached = None
    if pb:
        cached = tuple(
            as_bytes(fields[5 * nt + 1 + ti])[: uc * pb].reshape(uc, pb) if uc else None
            for ti, (_p, uc) in enumerate(dims)
        )
    return pairs, numvals, cached


def post_views(slab: np.ndarray, layout):
    """``(pairs, numvals, cached)``: NumPy views into a post slab; pairs
    is per tier ``(k1, k2, k3, req_id, uid)``, cached per tier the
    ``[Uc, PB] uint8`` block (None, whole, where the layout has none)."""
    return _split_post(slab, layout, lambda w: w.view(np.uint8))


def unpack_post_slab(slab, layout):
    """The same operands from the slab on the device (inside
    ``eval_post_tiered``)."""
    return _split_post(
        slab, layout, lambda w: lax.bitcast_convert_type(w, jnp.uint8).reshape(-1)
    )
