"""The optimized HLO of a rule set's matcher as the chip's compiler makes it, without the chip.

    JAX_PLATFORMS=cpu python3 hack/matcher_offline_hlo.py 32x512 [--rules DIR] [--opcodes cko.seg.embed,cko.seg.nce]

Describes a v5e (``jax.experimental.topologies``), makes ``ops/`` take its
TPU branch (``jax.default_backend`` answers the CPU here) and compiles
``cko_match_<rows>x<width>`` over shapes: nothing runs and no time comes
out of it. What comes out is what a launch is made of: one JSON line a
shape with ``device_ops`` (``observability/device_scopes.py``: total and by
scope; crs-lite ``32x512`` and crs-bodies ``32x2048`` gave the chip's own
counts to the operation, PR 42) and, for the scopes ``--opcodes`` names,
their operations by HLO opcode; the text goes to
``build/matcher_offline_<shape>.hlo``. A layout the compiler chose badly
shows as unfused ``concatenate`` and hundreds of ``copy`` under a scope
(PR 42 chose how to stack the gap classes on that, before any chip call).
crs-lite ``32x512``: 140 s, 3.7 GB. One process at a time may load the
TPU's library.

The conv tier's plan follows the device's memory
(``models/waf_model.py:seg_chunk_budget``) and the CPU here reports none,
so the model is told the described chip's (``--bytes-limit``, a v5e's
``memory_stats()["bytes_limit"]`` as the chip gave it in PR 46): the
plan traced is the chip's, and each line says it (``seg_plan``) beside
what the executable holds on the device (``memory``:
``compiled.memory_analysis()``).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

# ``jax.devices()[0].memory_stats()["bytes_limit"]`` of one v5e chip (my chip run, PR 46).
V5E_BYTES_LIMIT = 16_909_336_064


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("shapes", nargs="+", help="<rows>x<width>")
    ap.add_argument("--rules", default=str(REPO / "wafbench/configs/crs-lite-pl2/rules"))
    ap.add_argument("--opcodes", default="", help="scopes to split by opcode, comma-separated")
    ap.add_argument("--bytes-limit", type=int, default=V5E_BYTES_LIMIT,
                    help="the described device's memory, which the conv tier's plan follows")
    ap.add_argument("--plain-conv", action="store_true",
                    help="one tap a contraction (ops/segment.py:conv_tap_packing patched to 1), as until PR 47")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.models.slab import match_slab_shape
    from coraza_kubernetes_operator_tpu.models import waf_model
    from coraza_kubernetes_operator_tpu.models.waf_model import stage_executable
    from coraza_kubernetes_operator_tpu.observability import device_scopes
    from coraza_kubernetes_operator_tpu.ops import segment
    from wafbench.harness import read_rules

    if args.plain_conv:
        segment.conv_tap_packing = lambda spec: (1, spec.w)
    engine = WafEngine(read_rules(Path(args.rules)))
    one_chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"  # ops/ asks it which kernels to trace
    waf_model._device_bytes_limit = lambda: args.bytes_limit  # ... and the plan how much memory

    def described(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) if hasattr(a, "shape") else a

    model = jax.tree_util.tree_map(described, engine.model)
    h = max(1, len(engine._host_pipelines))
    dest = REPO / "build"
    dest.mkdir(exist_ok=True)
    for shape in args.shapes:
        rows, width = map(int, shape.split("x"))
        slab = jax.ShapeDtypeStruct(match_slab_shape(rows, width, h), jnp.uint8, sharding=one_chip)
        t0 = time.perf_counter()
        compiled = stage_executable("match", shape).lower(model, slab, mask=None).compile()
        text = compiled.as_text()
        (dest / f"matcher_offline_{shape}.hlo").write_text(text)
        names, inherited = device_scopes.walk(text)
        held = compiled.memory_analysis()
        line = {"shape": shape, "compile_s": time.perf_counter() - t0,
                "seg_plan": waf_model.tier_seg_plan(engine.model, rows, width).summary(),
                "memory": {"temp_bytes": held.temp_size_in_bytes,
                           "argument_bytes": held.argument_size_in_bytes,
                           "output_bytes": held.output_size_in_bytes},
                "device_ops": device_scopes.counts(names, inherited)}
        if args.opcodes:
            _entry, comps = device_scopes._parse(text)
            opcode = {i.name: i.opcode for body in comps.values() for i in body}
            line["opcodes"] = {
                scope: dict(collections.Counter(
                    opcode[n] for n, path in names.items() if device_scopes.scope_of(path) == scope
                ).most_common())
                for scope in args.opcodes.split(",")}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
