"""DFA hot tier: byte-class-packed transition-gather banks.

The two-level automata engine (docs/AUTOMATA.md) compiles groups whose
minimized DFAs are small — the analyzer's DFA-safety population — into
*joint-byte-class* dense tables and evaluates them as pure gathers. The
existing ``ops/dfa.py`` dense path keys its table by raw byte value
(``[256, S*G]``); here a bank-wide joint byte-class partition
(``compiler/re_dfa.joint_classmap``) first maps bytes onto C ≪ 256
classes, so the resident table is ``[C, S*G]`` — typically 4-8x smaller
— and the per-step contraction shrinks by the same factor. That's the
memory-layout codesign move (arXiv:2209.05686): size the table for VMEM
instead of trusting XLA's lowering of the 256-row form.

Three formulations, mirroring ``ops/dfa.py``:

- ``scan_gather_bank`` — dispatch. TPU + VMEM fit → the hand-written
  Pallas kernel (``ops/dfa_gather_pallas.py``); otherwise, or with
  ``CKO_PALLAS=0``, the jnp gather lowering below.
  ``CKO_PALLAS_INTERPRET=1`` forces the kernel in ``interpret=True``
  mode off-TPU so smokes exercise the exact kernel program on CPU.
- ``scan_gather_bank_jnp`` — the jnp gather lowering: per byte step a
  ``classmap`` gather (``[B]`` int32 from a 256-entry table) then a
  class-row ``take`` from the packed table, with the same
  state-sigma select as the take-scan. This is what XLA makes of the
  "gather" formulation; the Pallas kernel exists to beat it.
- the scalar oracle stays ``compiler/re_dfa.DFA.search`` — the property
  tests in tests/test_dfa_gather.py run both formulations against it.

Bank packing (``plan_gather_bins``) is greedy under two caps: the joint
class count (adding a dissimilar DFA to a bank coarsens nothing and
inflates C back toward 256) and the Pallas VMEM budget shared with
``ops/dfa.py``. One bin == one ``GatherBank`` == one maskable block in
the model's block order.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.re_dfa import DFA, joint_classmap
from .dfa import _PALLAS_BLOCK_B, _PALLAS_VMEM_BUDGET, _dense_dtype

_LANE = 128

# Greedy bin cap on joint classes: past one lane tile the class one-hot
# matmul stops shrinking relative to the 256-row form, so a new bank is
# cheaper than coarsening this one.
_MAX_JOINT_CLASSES = 120


@jax.tree_util.register_pytree_node_class
@dataclass
class GatherBank:
    """G stacked hot-tier DFAs sharing one joint byte-class partition.

    OPERAND DISCIPLINE (see ``ops/dfa.DFABank``): every table is a
    pytree LEAF and the aux is None, so executables are shared across
    tenants / hot reloads with same-shaped banks."""

    tC: jnp.ndarray  # [C, S*G] dense: next + S*emit (slot j = s*G + g)
    classmap: jnp.ndarray  # [256] int32 — joint byte -> class
    match_end: jnp.ndarray  # [G, S] bool
    always: jnp.ndarray  # [G] bool

    def tree_flatten(self):
        return (self.tC, self.classmap, self.match_end, self.always), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_groups(self) -> int:
        return int(self.match_end.shape[0])

    @property
    def n_states(self) -> int:
        return int(self.match_end.shape[1])

    @property
    def n_classes(self) -> int:
        return int(self.tC.shape[0])


def _pallas_knob() -> str:
    return os.environ.get("CKO_PALLAS", "1")


def _interpret_forced() -> bool:
    return os.environ.get("CKO_PALLAS_INTERPRET", "") == "1"


def stack_gather_bank(dfas: list[DFA], min_states: int = 1) -> GatherBank:
    """Stack hot-tier DFAs into one joint-class packed bank (host-side)."""
    g = len(dfas)
    s_max = max(min_states, max(d.n_states for d in dfas))
    classmap, remaps = joint_classmap(dfas)
    c = int(classmap.max()) + 1
    match_end = np.zeros((g, s_max), dtype=bool)
    always = np.zeros(g, dtype=bool)
    dense = np.zeros((c, s_max, g), dtype=np.int32)
    for i, (d, remap) in enumerate(zip(dfas, remaps)):
        s = d.n_states
        match_end[i, :s] = d.match_end
        always[i] = d.always_match
        per_class_next = d.trans[:, remap]  # [S, C]
        per_class_emit = d.emit[:, remap]  # [S, C]
        # Padded states self-loop to 0 and never activate (local state
        # starts at 0; transitions stay in [0, S)).
        dense[:, :s, i] = (
            per_class_next + s_max * per_class_emit.astype(np.int32)
        ).T
    dt, to_bf16 = _dense_dtype(s_max)
    tc = jnp.asarray(dense.reshape(c, s_max * g).astype(dt))
    if to_bf16 and jax.default_backend() == "tpu":
        tc = tc.astype(jnp.bfloat16)
    return GatherBank(
        tC=tc,
        classmap=jnp.asarray(classmap),
        match_end=jnp.asarray(match_end),
        always=jnp.asarray(always),
    )


def _gather_vmem_bytes(
    s: int, g: int, c: int, itemsize: int, length: int
) -> int:
    """Resident working-set estimate for the gather kernel — same budget
    ledger as ``ops/dfa._pallas_vmem_bytes`` (11 MB, hardware-proven; do
    not raise, see the warning there)."""
    gp = (g + _LANE - 1) // _LANE * _LANE
    cp = (c + _LANE - 1) // _LANE * _LANE
    cls256 = 256 * cp * itemsize  # byte -> class one-hot
    table = cp * s * gp * itemsize
    # per-step [block_b, S*Gp] accumulator + fused select intermediate,
    # plus the [block_b, Cp] class one-hot
    work = _PALLAS_BLOCK_B * s * gp * 4 * 2 + _PALLAS_BLOCK_B * cp * 4
    data_tile = length * _PALLAS_BLOCK_B * 4 * 2
    return cls256 + table + work + data_tile


def plan_gather_bins(dfas: list[DFA], length_hint: int = 512) -> list[list[int]]:
    """Greedy packing of hot-tier DFAs into gather banks. Returns index
    bins (into ``dfas``); each bin becomes one ``GatherBank``. Caps: the
    joint class count (``_MAX_JOINT_CLASSES``) and the shared Pallas
    VMEM budget at ``length_hint`` bytes per row."""
    order = sorted(range(len(dfas)), key=lambda i: (dfas[i].n_states, i))
    bins: list[list[int]] = []
    # Per bin its joint byte classes so far ([256] ids) and widest DFA:
    # a candidate's joint class count is the distinct (bin class, own
    # class) pairs over the 256 bytes — what ``joint_class_count`` of the
    # whole bin would say, without restacking the bin per candidate (that
    # was 56 s of a 5,000-rule feed's install, PR 37).
    joint: list[np.ndarray] = []
    widest: list[int] = []
    for idx in order:
        d = dfas[idx]
        own = d.classmap.astype(np.int64)
        placed = False
        for k, bin_ in enumerate(bins):
            pairs, inv = np.unique(joint[k] * 256 + own, return_inverse=True)
            c = int(pairs.shape[0])
            if c > _MAX_JOINT_CLASSES:
                continue
            s = max(widest[k], d.n_states)
            dt, _ = _dense_dtype(s)
            if (
                _gather_vmem_bytes(s, len(bin_) + 1, c, np.dtype(dt).itemsize, length_hint)
                > _PALLAS_VMEM_BUDGET
            ):
                continue
            bin_.append(idx)
            joint[k], widest[k] = inv.reshape(-1).astype(np.int64), s
            placed = True
            break
        if not placed:
            bins.append([idx])
            joint.append(np.unique(own, return_inverse=True)[1].reshape(-1).astype(np.int64))
            widest.append(d.n_states)
    # Deterministic model layout: bins ordered by first member gid.
    for bin_ in bins:
        bin_.sort()
    bins.sort(key=lambda b: b[0])
    return bins


def scan_gather_bank(
    bank: GatherBank, data: jnp.ndarray, lengths: jnp.ndarray, name: str | None = None
) -> jnp.ndarray:
    """Scan ``data`` [B, L] uint8 (zero-padded past ``lengths`` [B])
    against every hot-tier DFA in the bank. Returns matched [B, G] bool.

    Dispatch: Pallas VMEM-resident gather kernel on TPU when the packed
    table + working set fit the shared VMEM budget; the jnp gather
    lowering otherwise or when ``CKO_PALLAS=0``. Off-TPU,
    ``CKO_PALLAS_INTERPRET=1`` runs the kernel via
    ``pallas_call(interpret=True)`` so CI exercises the exact kernel
    program on CPU."""
    if _pallas_knob() == "0":
        return scan_gather_bank_jnp(bank, data, lengths)
    fits = (
        _gather_vmem_bytes(
            bank.n_states,
            bank.n_groups,
            bank.n_classes,
            bank.tC.dtype.itemsize,
            data.shape[1],
        )
        <= _PALLAS_VMEM_BUDGET
    )
    on_tpu = jax.default_backend() == "tpu"
    if fits and (on_tpu or _interpret_forced()):
        from .dfa_gather_pallas import scan_gather_bank_pallas

        return scan_gather_bank_pallas(
            bank.tC,
            bank.classmap,
            bank.match_end.T,
            bank.always,
            data,
            lengths,
            s=bank.n_states,
            g=bank.n_groups,
            c=bank.n_classes,
            block_b=_PALLAS_BLOCK_B,
            name=name,
        )
    return scan_gather_bank_jnp(bank, data, lengths)


@partial(jax.jit, static_argnames=())
def scan_gather_bank_jnp(
    bank: GatherBank, data: jnp.ndarray, lengths: jnp.ndarray
) -> jnp.ndarray:
    """The jnp gather lowering: per byte step a joint-classmap gather
    then a class-row ``take`` from the packed table, state-sigma select
    on the VPU. Correct everywhere (CPU path and ``CKO_PALLAS=0``
    fallback); materializes a [B, S*G] intermediate per step, which is
    exactly what the Pallas kernel keeps in VMEM."""
    b, length = data.shape
    g = bank.n_groups
    s = bank.n_states

    state_iota = jnp.arange(s, dtype=jnp.int32)[None, :, None]  # [1, S, 1]

    # Varying-zero init derived from the operands (shard_map carry rule —
    # see ops/dfa.scan_dfa_bank_take).
    row0 = (
        data[:, :1].astype(jnp.int32) * 0 + bank.tC[:1, :1].astype(jnp.int32) * 0
    )  # [B, 1]
    zero2 = row0 + jnp.zeros((b, g), dtype=jnp.int32)  # [B, G]
    init = (zero2, zero2 != 0, zero2)

    def step(carry, xs):
        t, byte_col = xs
        state, matched, end_state = carry
        cls = bank.classmap[byte_col.astype(jnp.int32)]  # [B] gather
        r = jnp.take(bank.tC, cls, axis=0)  # [B, S*G] row gather
        r = r.astype(jnp.int32).reshape(b, s, g)
        sigma = state[:, None, :] == state_iota  # [B, S, G]
        val = jnp.sum(jnp.where(sigma, r, 0), axis=1).astype(jnp.int32)
        hit = val >= s
        nxt = val - s * hit.astype(jnp.int32)
        active = (t < lengths)[:, None]
        matched = matched | (hit & active)
        state = jnp.where(active, nxt, state)
        end_state = jnp.where((t == lengths - 1)[:, None], state, end_state)
        return (state, matched, end_state), None

    ts = jnp.arange(length, dtype=jnp.int32)
    (state, matched, end_state), _ = jax.lax.scan(step, init, (ts, data.T))
    end_sigma = end_state[:, None, :] == state_iota
    end_match = jnp.any(end_sigma & bank.match_end.T[None, :, :], axis=1)
    matched = matched | end_match
    return matched | bank.always[None, :]
