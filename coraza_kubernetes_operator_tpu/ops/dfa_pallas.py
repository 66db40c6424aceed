"""Pallas TPU kernel for the stacked-DFA bank scan.

Why a custom kernel: the XLA formulations of this scan are all memory-bound
or miscompiled —

- the original two-gathers-per-byte scan serializes on TPU (~611 ms for a
  [4096, 64] batch against 155 DFAs);
- a per-step one-hot @ table matmul is miscompiled *inside* ``lax.scan`` at
  batch sizes around 4096-5000 (identical wrong results on XLA:CPU and
  XLA:TPU; correct when the step runs standalone — see
  ``tests/test_dfa_kernel.py::test_matmul_scan_xla_miscompile_guard``);
- a per-step row-gather (``take``) formulation is correct but materializes a
  ``[B, S*G]`` int32 intermediate in HBM every byte step (~68 MB → ~8.7 GB
  of HBM traffic for 64 steps), measured at ~118 ms.

The kernel keeps the dense transition table (``[256, S*Gp]`` int8, ~1-2 MB
for a CRS-sized bank) and the per-block DFA state in VMEM for the whole
byte loop, so per-step intermediates never touch HBM. Per step it does one
``[Bt, 256] @ [256, S*Gp]`` int8 MXU dot (the byte one-hot *is* the table
row select) and a VPU state-select/compare — the classic
lookup-as-matmul trick, which is how a DFA transition maps onto a systolic
array.

Layout: states are S-major / groups G-minor, G padded to a lane multiple
(128); the accumulator reshape ``[Bt, S*Gp] -> [Bt, S, Gp]`` then keeps the
lane dimension 128-aligned.

Used for any dense-table bank whose working set (table + per-step
accumulator + dataT tile at block_b=128) fits the VMEM budget in
``ops/dfa.py:_pallas_vmem_bytes``; banks beyond it fall back to the XLA
``take`` scan. CPU tests run the kernel in interpreter mode on small
shapes; the tiered dispatch is in ``ops/dfa.py:scan_dfa_bank``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _scan_kernel(dataT_ref, len_ref, t256_ref, mend_ref, out_ref, *, s, gp, length):
    """One grid step: scan a [Bt] row-block over all `length` bytes.

    dataT_ref: [L, Bt] int32 — byte columns (transposed so each step reads a
        lane-contiguous row).
    len_ref: [Bt, 1] int32; t256_ref: [256, S*Gp]; mend_ref: [S, Gp] int32
    (end-of-input match mask); out_ref: [Bt, Gp] int32.
    """
    bt = out_ref.shape[0]
    in_dt = t256_ref.dtype
    acc_dt = jnp.int32 if in_dt == jnp.int8 else jnp.float32
    lengths = len_ref[:, 0][:, None]  # [Bt, 1]
    bytes_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, 256), 1)
    state_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, s, gp), 1)

    def step(t, carry):
        state, matched, end_state = carry  # [Bt, Gp] i32 each
        byte = dataT_ref[t, :][:, None]  # [Bt, 1]
        onehot = (byte == bytes_iota).astype(in_dt)  # [Bt, 256]
        r = jnp.dot(onehot, t256_ref[:], preferred_element_type=acc_dt)
        r = r.reshape(bt, s, gp)
        sigma = state[:, None, :] == state_iota  # [Bt, S, Gp]
        val = jnp.sum(jnp.where(sigma, r, 0), axis=1).astype(jnp.int32)
        hit = (val >= s).astype(jnp.int32)
        nxt = val - s * hit
        active = (t < lengths).astype(jnp.int32)  # [Bt, 1]
        matched = matched | (hit & active)
        state = jnp.where(active != 0, nxt, state)
        end_state = jnp.where(t == lengths - 1, state, end_state)
        return state, matched, end_state

    zero = jnp.zeros((bt, gp), dtype=jnp.int32)
    state, matched, end_state = jax.lax.fori_loop(
        0, length, step, (zero, zero, zero)
    )
    end_sigma = end_state[:, None, :] == state_iota
    end_hit = jnp.sum(
        jnp.where(end_sigma, mend_ref[:][None, :, :], 0), axis=1
    )
    out_ref[:] = matched | (end_hit > 0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("s", "g", "block_b", "interpret", "name")
)
def scan_dfa_bank_pallas(
    t256: jnp.ndarray,  # [256, S*G]
    match_end_t: jnp.ndarray,  # [S, G] bool
    always: jnp.ndarray,  # [G] bool
    data: jnp.ndarray,  # [B, L] uint8
    lengths: jnp.ndarray,  # [B] int32
    *,
    s: int,
    g: int,
    block_b: int = 128,
    interpret: bool | None = None,
    name: str | None = None,
) -> jnp.ndarray:
    """Bank scan via the Pallas kernel. Returns matched [B, G] bool.
    ``name`` is the kernel's name in a device trace (the caller's bank
    index; XLA's running counter otherwise)."""
    b, length = data.shape
    gp = _round_up(g, _LANE)
    bp = _round_up(max(b, block_b), block_b)

    # Pad G (lane alignment) and B (grid) — padded groups/rows never match.
    t3 = t256.reshape(256, s, g)
    t3 = jnp.pad(t3, ((0, 0), (0, 0), (0, gp - g))).reshape(256, s * gp)
    mend = jnp.pad(match_end_t.astype(jnp.int32), ((0, 0), (0, gp - g)))
    dataT = jnp.pad(data.astype(jnp.int32), ((0, bp - b), (0, 0))).T  # [L, Bp]
    lens = jnp.pad(lengths.astype(jnp.int32), (0, bp - b))[:, None]  # [Bp, 1]

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(_scan_kernel, s=s, gp=gp, length=length)
    out = pl.pallas_call(
        kernel,
        grid=(bp // block_b,),
        in_specs=[
            pl.BlockSpec((length, block_b), lambda i: (0, i)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((256, s * gp), lambda i: (0, 0)),
            pl.BlockSpec((s, gp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, gp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, gp), jnp.int32),
        interpret=interpret,
        name=name,
    )(dataT, lens, t3, mend)
    return (out[:b, :g] != 0) | always[None, :]
