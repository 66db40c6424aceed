"""Stacked-DFA batch scanner: the plain bank scan.

A bank stacks G compiled DFAs (``compiler/re_dfa.py``) into device tables
and scans a ``[B, L]`` byte batch in one ``lax.scan``. The served path
matches its dense-DFA blocks in the fused flat bins (``ops/dfa_flat.py``);
this module stays as (a) the scan of a block no bin holds and of the conv
tier's long banks (``models/waf_model.py``), (b) the oracle
``tests/test_dfa_flat.py`` holds the bins to, and (c) what
``parallel/mesh.py`` shards. Two formulations, picked by ``scan_dfa_bank``
from the bank alone, the same on every backend:

- ``scan_dfa_bank_take``: a bank of at most ``_DENSE_MAX_STATES`` padded
  states carries a dense per-slot table ``[256, S*G]`` whose entries pack
  ``next + S*emit`` (int8 when the packed values fit, S <= 64; else f32,
  cast to bf16 on a TPU while S <= 128: integers exact to 256). A byte
  step is one row ``take`` and a VPU select by the current state.
- ``scan_dfa_bank_gather``: two gathers a byte (classmap, then the packed
  ``[G, S, C]`` table). The semantic oracle of the differential tests and
  the scan of a bank too wide for a dense table, which on a TPU
  serializes: the prefilter (``compiler/re_approx.py``) exists to keep
  such groups off it.

Long bodies stream through the same scan: DFA state is the natural carry,
which is the blockwise "long context" decomposition (SURVEY §5); the carry
crosses block boundaries exactly.

Groups are bucketed by table size before stacking (``stack_dfas`` callers
pad to the bank max), trading padding waste for a single fused scan.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.re_dfa import DFA

_EMIT_SHIFT = 30
_STATE_MASK = (1 << _EMIT_SHIFT) - 1


@jax.tree_util.register_pytree_node_class
@dataclass
class DFABank:
    """G stacked DFAs, padded to common [S, C].

    OPERAND DISCIPLINE (shape-canonical executable reuse,
    ``engine/compile_cache.py``): every table is a pytree LEAF — a
    runtime operand — and the aux is None. Moving a table into the aux
    (or closing over it as a trace-time constant) would bake ruleset
    content into the executable and break cross-tenant / hot-reload
    executable sharing; keep new fields leaves unless they change the
    traced computation's structure."""

    packed: jnp.ndarray  # [G, S, C] int32: next_state | (emit << 30)
    classmap: jnp.ndarray  # [256, G] int32 (transposed for row gather)
    match_end: jnp.ndarray  # [G, S] bool
    always: jnp.ndarray  # [G] bool
    t256: jnp.ndarray  # [256, S*G] dense: next + S*emit (slot j = s*G + g)

    def tree_flatten(self):
        return (self.packed, self.classmap, self.match_end, self.always, self.t256), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_groups(self) -> int:
        return int(self.packed.shape[0])

    @property
    def n_states(self) -> int:
        return int(self.packed.shape[1])


# Max padded state count for which the dense byte-indexed table is built.
# Beyond this the packed value no longer fits narrow dtypes and the table
# itself becomes a (256/C)x memory blow-up over the class-compressed form;
# such banks scan via the classmap gather path instead.
_DENSE_MAX_STATES = 128


def _dense_dtype(s_max: int):
    """(numpy dtype, cast-to-bf16-on-TPU) for packed values in [0, 2*s_max)."""
    if 2 * s_max - 1 <= 127:
        return np.int8, False
    return np.float32, 2 * s_max - 1 <= 255  # bf16 holds integers <= 256 exactly


def stack_dfas(dfas: list[DFA], min_states: int = 1) -> DFABank:
    """Stack DFAs into one padded bank (host-side, numpy). ``min_states``
    forces a larger state padding so shard banks can share one layout."""
    g = len(dfas)
    s_max = max(min_states, max(d.n_states for d in dfas))
    c_max = max(d.n_classes for d in dfas)
    packed = np.zeros((g, s_max, c_max), dtype=np.int32)
    classmap = np.zeros((256, g), dtype=np.int32)
    match_end = np.zeros((g, s_max), dtype=bool)
    always = np.zeros(g, dtype=bool)
    build_dense = s_max <= _DENSE_MAX_STATES
    # Dense byte-indexed table for the take-scan: for every byte
    # value and (state, group) slot, the packed next-state + S*emit. Padded
    # states (s >= d.n_states) self-loop to 0 and never activate (state
    # one-hot starts at local state 0 and transitions stay in range).
    dense = np.zeros((256, s_max if build_dense else 0, g), dtype=np.int32)
    for i, d in enumerate(dfas):
        s, c = d.n_states, d.n_classes
        packed[i, :s, :c] = d.trans.astype(np.int32) | (
            d.emit.astype(np.int32) << _EMIT_SHIFT
        )
        classmap[:, i] = d.classmap
        match_end[i, :s] = d.match_end
        always[i] = d.always_match
        if build_dense:
            per_byte_next = d.trans[:, d.classmap]  # [S, 256]
            per_byte_emit = d.emit[:, d.classmap]  # [S, 256]
            dense[:, :s, i] = (
                per_byte_next + s_max * per_byte_emit.astype(np.int32)
            ).T
    t256 = dense.reshape(256, dense.shape[1] * g)
    dt, to_bf16 = _dense_dtype(s_max)
    t256_j = jnp.asarray(t256.astype(dt))
    if to_bf16 and jax.default_backend() == "tpu":
        t256_j = t256_j.astype(jnp.bfloat16)
    return DFABank(
        packed=jnp.asarray(packed),
        classmap=jnp.asarray(classmap),
        match_end=jnp.asarray(match_end),
        always=jnp.asarray(always),
        t256=t256_j,
    )


def scan_dfa_bank(bank: DFABank, data: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Scan ``data`` [B, L] uint8 (zero-padded past ``lengths`` [B]) against
    every DFA in the bank. Returns ``matched`` [B, G] bool.

    The dense-row take-scan where the bank has a dense table; the classmap
    gather scan for a huge-state bank (no dense table: it would be a
    (256/C)x memory blow-up)."""
    if bank.t256.size == 0:
        return scan_dfa_bank_gather(bank, data, lengths)
    return scan_dfa_bank_take(bank, data, lengths)


@partial(jax.jit, static_argnames=())
def scan_dfa_bank_take(
    bank: DFABank, data: jnp.ndarray, lengths: jnp.ndarray
) -> jnp.ndarray:
    """XLA formulation: per byte step a row-gather from the dense table
    (``take``) and a VPU state-select. Materializes a [B, S*G]
    intermediate in HBM per step, which the flat bins keep in VMEM for the
    blocks they hold. (A one-hot @ table matmul inside ``lax.scan``
    is NOT used: XLA miscompiles it at batch ~4096-5000, identically on CPU
    and TPU; see tests/test_dfa_kernel.py.)"""
    b, length = data.shape
    g = bank.n_groups
    s = bank.n_states

    state_iota = jnp.arange(s, dtype=jnp.int32)[None, :, None]  # [1, S, 1]

    # Derive the zero init from the inputs so the carry inherits their
    # varying-manual-axes property under shard_map (a plain jnp.zeros is
    # 'unvarying' and lax.scan rejects the carry type mismatch). Both the
    # data (data-sharded) and the tables (rule-sharded) contribute axes.
    row0 = (
        data[:, :1].astype(jnp.int32) * 0 + bank.t256[:1, :1].astype(jnp.int32) * 0
    )  # [B, 1] varying zero
    zero2 = row0 + jnp.zeros((b, g), dtype=jnp.int32)  # [B, G]
    init = (zero2, zero2 != 0, zero2)

    def step(carry, xs):
        t, byte_col = xs
        state, matched, end_state = carry
        r = jnp.take(bank.t256, byte_col.astype(jnp.int32), axis=0)
        r = r.astype(jnp.int32).reshape(b, s, g)
        sigma = state[:, None, :] == state_iota  # [B, S, G] bool
        val = jnp.sum(jnp.where(sigma, r, 0), axis=1).astype(jnp.int32)  # [B, G]
        hit = val >= s
        nxt = val - s * hit.astype(jnp.int32)
        active = (t < lengths)[:, None]  # [B, 1]
        matched = matched | (hit & active)
        state = jnp.where(active, nxt, state)
        end_state = jnp.where((t == lengths - 1)[:, None], state, end_state)
        return (state, matched, end_state), None

    ts = jnp.arange(length, dtype=jnp.int32)
    (state, matched, end_state), _ = jax.lax.scan(step, init, (ts, data.T))
    end_sigma = end_state[:, None, :] == state_iota  # [B, S, G]
    end_match = jnp.any(end_sigma & bank.match_end.T[None, :, :], axis=1)
    matched = matched | end_match
    return matched | bank.always[None, :]


@partial(jax.jit, static_argnames=())
def scan_dfa_bank_gather(
    bank: DFABank, data: jnp.ndarray, lengths: jnp.ndarray
) -> jnp.ndarray:
    """Original gather-per-byte formulation — differential-test oracle."""
    b = data.shape[0]
    g = bank.n_groups
    garange = jnp.arange(g, dtype=jnp.int32)[None, :]  # [1, G]

    def step(carry, t):
        state, matched, end_state = carry
        byte = data[:, t].astype(jnp.int32)  # [B]
        cls = bank.classmap[byte]  # [B, G]
        packed = bank.packed[garange, state, cls]  # [B, G]
        active = (t < lengths)[:, None]  # [B, 1]
        hit = (packed >> _EMIT_SHIFT).astype(bool)
        matched = matched | (hit & active)
        state = jnp.where(active, packed & _STATE_MASK, state)
        end_state = jnp.where((t == lengths - 1)[:, None], state, end_state)
        return (state, matched, end_state), None

    row0 = (
        data[:, :1].astype(jnp.int32) * 0 + bank.packed[0, 0, 0] * 0
    )  # [B, 1] varying zero
    init = (
        jnp.zeros((b, g), dtype=jnp.int32) + row0,
        jnp.zeros((b, g), dtype=bool) | (row0 != 0),
        jnp.zeros((b, g), dtype=jnp.int32) + row0,
    )
    (state, matched, end_state), _ = jax.lax.scan(
        step, init, jnp.arange(data.shape[1], dtype=jnp.int32)
    )
    matched = matched | bank.match_end[garange, end_state]
    return matched | bank.always[None, :]
