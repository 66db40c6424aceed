"""The conv tier's embed and convolution alone, by block: one tap a
contraction, the program's packing, and space-to-depth (ISSUE 47).

    python3 hack/conv_pack_probe.py [--blocks 26x36x9989@32x2048 ...] [--forms plain packed s2d]

On the device JAX finds (a TPU through the builder's chip tool): for each
block ``<W taps>x<C channels>x<N2 columns>@<rows>x<width>`` steps 1 and 2
of ``ops/segment.py:match_segment_block`` (the C comparison planes of the
bytes, stacked; the convolution to the ``[T, Q, N2]`` bf16 output) are
jitted alone and timed, ``REPEAT`` applications inside ONE executable:

- ``plain``: the program's own two steps with ``conv_tap_packing`` patched
  to one tap a contraction, C channels deep (the program until PR 47);
- ``packed``: the program's own two steps as shipped: ``k = 128 // C``
  taps a contraction, the conv dilated by k;
- ``s2d``: the alternative the issue asked to price, which the program
  does not hold: space-to-depth, no dilation: the plain stack reshaped to
  ``[T, P / k, k·C]``, k phase kernels side by side on the column axis, a
  stride-1 conv of ``ceil((W + k - 1) / k)`` taps to ``[T, Q / k, k·N2]``
  and a reshape back.

One JSON line a block and form: ms an application, the useful flops
(2·T·Q·W·C·N2) over that time as a share of ``wafbench/peaks.json``'s
``bf16_flops_per_s``, and the largest difference from ``plain`` (0.0: the
sums are whole numbers of at most 2W). Alone means without the matcher
around it: what the chains want of the output's layout is
``hack/matcher_shape_probe.py --scopes``' to say.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

CALLS, REPEAT = 8, 4
# The feed's block 0 and its agents, crs-lite's blocks 0, 2, 3 and 6 (ISSUE 47's table).
BLOCKS = ["26x36x9989@32x2048", "26x36x9989@32x512", "24x26x1076@32x2048", "24x42x503@512x512",
          "26x36x988@512x512", "26x27x285@512x512", "11x16x3@512x512", "24x42x503@32x512"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", nargs="+", default=BLOCKS, help="<W>x<C>x<N2>@<rows>x<width>")
    ap.add_argument("--forms", nargs="+", default=["plain", "packed", "s2d"])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from coraza_kubernetes_operator_tpu.ops import segment

    dev = jax.devices()[0]
    peaks = json.loads((REPO / "wafbench/peaks.json").read_text())["peaks"].get(dev.device_kind)
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform, "peaks": peaks}), flush=True)
    rng = np.random.default_rng(47)
    packing = segment.conv_tap_packing

    def spec_of(w, c, n2):
        """A block's statics as far as steps 1 and 2 read them: nibbles, a ones plane, intervals."""
        chans = [("hi", i) for i in range(16)] + [("lo", i) for i in range(16)] + [("one",)]
        chans += [("ind", ((48 + i, 57 + i),)) for i in range(c - 33)]
        return segment.SegmentSpec(w=w, n_seg=n2, channels=tuple(chans[:c]), seg_meta=(), branches=(),
                                   always=(), n_groups=0)

    def once(form, spec, data, kernel):
        if form != "s2d":  # the program's: ``conv_tap_packing`` is read at trace time
            segment.conv_tap_packing = packing if form == "packed" else (lambda sp: (1, sp.w))
            try:
                return segment._conv_taps(spec, segment._embed_taps(spec, data)[1], kernel)
            finally:
                segment.conv_tap_packing = packing
        t, ln = data.shape
        w, c, q, n2 = spec.w, len(spec.channels), ln + 2, kernel.shape[2]
        k = packing(spec)[0]
        taps = -(-(w + k - 1) // k)
        span = -(-q // k) + taps - 1
        d = jnp.pad(data, ((0, 0), (1, k * span - ln - 1))).astype(jnp.int32)
        embed = jnp.stack([segment._channel_plane(ch, d) for ch in spec.channels], axis=-1)
        embed = embed.astype(jnp.bfloat16).reshape(t, span, k * c)
        phases = [jnp.pad(kernel, ((s, k * taps - w - s), (0, 0), (0, 0))).reshape(taps, k * c, n2)
                  for s in range(k)]
        out = jax.lax.conv_general_dilated(
            embed, jnp.concatenate(phases, axis=-1), window_strides=(1,), padding="VALID",
            dimension_numbers=("NWC", "WIO", "NWC"), preferred_element_type=jnp.bfloat16)
        return out.reshape(t, -1, n2)[:, :q]

    def repeated(form, spec):
        def fn(data, kernel):
            def step(_i, carry):
                d, _out = carry
                out = once(form, spec, d, kernel)
                # No byte changes (a sum is at most 2W), but the next application waits for this one.
                return d + (out[:, : d.shape[1], 0] > 1e4).astype(d.dtype), out
            first = once(form, spec, data, kernel)
            return jax.lax.fori_loop(0, REPEAT - 1, step, (data, first))[1]
        return fn

    for block in args.blocks:
        dims, shape = block.split("@")
        w, c, n2 = map(int, dims.split("x"))
        t, ln = map(int, shape.split("x"))
        data = jnp.asarray(rng.integers(0x20, 0x7F, (t, ln), dtype=np.uint8))
        kernel = jnp.asarray(rng.integers(0, 3, (w, c, n2)), dtype=jnp.bfloat16)
        flops = 2 * t * (ln + 2) * w * c * n2
        spec = spec_of(w, c, n2)
        plain = None
        for form in args.forms:
            t0 = time.perf_counter()
            compiled = jax.jit(repeated(form, spec)).lower(data, kernel).compile()
            compile_s = time.perf_counter() - t0
            out = jax.block_until_ready(compiled(data, kernel))
            ms = []
            for _ in range(CALLS):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(data, kernel))
                ms.append(1e3 * (time.perf_counter() - t0))
            each = statistics.median(ms) / REPEAT
            line = {"block": block, "form": form, "taps_a_contraction": 1 if form == "plain" else packing(spec)[0],
                    "ms": each, "compile_s": compile_s,
                    "useful_flops": flops,
                    "share_of_bf16_peak": flops / (each / 1e3) / peaks["bf16_flops_per_s"] if peaks else None}
            if form == "plain":
                plain = out
            elif plain is not None:
                line["max_abs_diff_from_plain"] = float(jnp.max(jnp.abs(
                    out.astype(jnp.float32) - plain.astype(jnp.float32))))
            del out
            print(json.dumps(line), flush=True)
        del plain
    return 0


if __name__ == "__main__":
    sys.exit(main())
