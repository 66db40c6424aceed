"""Pallas TPU kernel for the joint-byte-class transition-gather scan.

The hot-tier inner loop, hand-written instead of trusting XLA's
lowering (the jnp form in ``ops/dfa_gather.py`` materializes a
``[B, S*G]`` row-gather intermediate in HBM every byte step). The
kernel keeps BOTH tables resident in VMEM for the whole byte loop:

- the byte → joint-class one-hot ``[256, Cp]``;
- the class-indexed packed transition table ``[Cp, S*Gp]``.

Per step it does TWO MXU dots instead of ``ops/dfa_pallas.py``'s one:
``[Bt, 256] @ [256, Cp]`` turns the byte one-hot into the class one-hot
(the classmap gather as a matmul), then ``[Bt, Cp] @ [Cp, S*Gp]``
selects the packed transition row. Because C ≪ 256 for a
well-packed bank, the second (dominant) contraction and the resident
table both shrink by 256/C versus the byte-indexed kernel — that is the
VMEM-codesign payoff: more hot banks fit the (hardware-proven, 11 MB)
budget and each step moves fewer bytes.

dtype: int8 end-to-end when packed values fit (S ≤ 64 — the planner's
default hot ceiling — rides the int8 MXU); else f32, cast to bf16 on
TPU when exact (S ≤ 128). Class one-hots are 0/1 so every intermediate
is exact in all three dtypes.

``interpret=True`` (automatic off-TPU, forced via
``CKO_PALLAS_INTERPRET=1`` in the dispatcher) is the CPU/test path: the
differential tests and the automata smoke run this exact kernel program
against the scalar oracle without hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _gather_kernel(
    dataT_ref, len_ref, cls_ref, tc_ref, mend_ref, out_ref, *, s, gp, length
):
    """One grid step: scan a [Bt] row-block over all ``length`` bytes.

    dataT_ref: [L, Bt] int32 — byte columns (lane-contiguous per step).
    len_ref: [Bt, 1] int32; cls_ref: [256, Cp] byte→class one-hot;
    tc_ref: [Cp, S*Gp] packed next + S*emit; mend_ref: [S, Gp] int32;
    out_ref: [Bt, Gp] int32.
    """
    bt = out_ref.shape[0]
    in_dt = tc_ref.dtype
    acc_dt = jnp.int32 if in_dt == jnp.int8 else jnp.float32
    lengths = len_ref[:, 0][:, None]  # [Bt, 1]
    bytes_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, 256), 1)
    state_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, s, gp), 1)

    def step(t, carry):
        state, matched, end_state = carry  # [Bt, Gp] i32 each
        byte = dataT_ref[t, :][:, None]  # [Bt, 1]
        onehot = (byte == bytes_iota).astype(in_dt)  # [Bt, 256]
        # classmap gather as a matmul: exactly one 1 per row, so the
        # class one-hot is exact in int8/bf16/f32 alike.
        clsoh = jnp.dot(onehot, cls_ref[:], preferred_element_type=acc_dt)
        r = jnp.dot(
            clsoh.astype(in_dt), tc_ref[:], preferred_element_type=acc_dt
        )
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
    end_hit = jnp.sum(jnp.where(end_sigma, mend_ref[:][None, :, :], 0), axis=1)
    out_ref[:] = matched | (end_hit > 0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("s", "g", "c", "block_b", "interpret", "name")
)
def scan_gather_bank_pallas(
    tc: jnp.ndarray,  # [C, S*G] packed
    classmap: jnp.ndarray,  # [256] int32 joint classes
    match_end_t: jnp.ndarray,  # [S, G] bool
    always: jnp.ndarray,  # [G] bool
    data: jnp.ndarray,  # [B, L] uint8
    lengths: jnp.ndarray,  # [B] int32
    *,
    s: int,
    g: int,
    c: int,
    block_b: int = 128,
    interpret: bool | None = None,
    name: str | None = None,
) -> jnp.ndarray:
    """Hot-tier bank scan via the transition-gather kernel. Returns
    matched [B, G] bool. ``name`` is the kernel's name in a device
    trace (the caller's bank index; XLA's running counter otherwise)."""
    b, length = data.shape
    gp = _round_up(g, _LANE)
    cp = _round_up(c, _LANE)
    bp = _round_up(max(b, block_b), block_b)

    # Byte → class one-hot, padded on the class axis; padded classes have
    # no bytes and padded table rows are zero, so they contribute nothing.
    in_dt = tc.dtype
    clsoh = (
        classmap[:, None] == jnp.arange(cp, dtype=jnp.int32)[None, :]
    ).astype(in_dt)  # [256, Cp]
    t3 = tc.reshape(c, s, g)
    t3 = jnp.pad(t3, ((0, cp - c), (0, 0), (0, gp - g))).reshape(cp, s * gp)
    mend = jnp.pad(match_end_t.astype(jnp.int32), ((0, 0), (0, gp - g)))
    dataT = jnp.pad(data.astype(jnp.int32), ((0, bp - b), (0, 0))).T  # [L, Bp]
    lens = jnp.pad(lengths.astype(jnp.int32), (0, bp - b))[:, None]  # [Bp, 1]

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(_gather_kernel, s=s, gp=gp, length=length)
    out = pl.pallas_call(
        kernel,
        grid=(bp // block_b,),
        in_specs=[
            pl.BlockSpec((length, block_b), lambda i: (0, i)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((256, cp), lambda i: (0, 0)),
            pl.BlockSpec((cp, s * gp), lambda i: (0, 0)),
            pl.BlockSpec((s, gp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, gp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, gp), jnp.int32),
        interpret=interpret,
        name=name,
    )(dataT, lens, clsoh, t3, mend)
    return (out[:b, :g] != 0) | always[None, :]
