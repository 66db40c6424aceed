"""How a rule set's matcher executable's time moves with rows and width.

    python3 hack/matcher_shape_probe.py 8x512 64x512 8x2048 64x2048 [--rules DIR]

On the device JAX finds (a TPU through the builder's chip tool; the CPU
cannot compile full crs-lite): builds the engine, compiles
``jit_cko_match_<rows>x<width>`` for each shape (in parallel: XLA
releases the interpreter lock), then calls each 20 times with the
model's tables resident and waits for the result, twice: every row as
long as the tier is wide, and every row 32 bytes long in the same tier.
Wall time per call is device time here: nothing else runs, and a call
is tens of milliseconds against tens of microseconds of dispatch. The
second line says what a call launches (``automata_summary()``: flat
bins, their slots and groups, blocks left on one kernel a bank). One
JSON line per shape, all of them again in
``chiprun_out/matcher_shape_probe.json``. ROADMAP Speed 2's question
("fixed, or grows with rows?") is answered by the lines it prints.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("shapes", nargs="+", help="<rows>x<width>")
    ap.add_argument("--rules", default=str(REPO / "wafbench/configs/crs-lite-pl2/rules"))
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.models.slab import match_slab_shape, match_views
    from coraza_kubernetes_operator_tpu.models.waf_model import stage_executable
    from wafbench.harness import read_rules

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "JAX_COMPILATION_CACHE_DIR": os.environ.get("JAX_COMPILATION_CACHE_DIR")}),
          flush=True)
    engine = WafEngine(read_rules(Path(args.rules)))
    # What each call launches: one Pallas kernel a flat bin and one a
    # dense-DFA block no bin covers (none for crs-lite since PR 31).
    layout = engine.automata_summary()
    print(json.dumps({k: layout[k] for k in ("rules", "segment_columns", "segment_splits",
                                             "segment_split_groups", "flat_bins", "flat_slots",
                                             "flat_groups", "per_bank_kernels")}), flush=True)
    model = jax.device_put(engine.model)
    h = max(1, len(engine._host_pipelines))
    rng = np.random.default_rng(28)

    def operands(rows: int, width: int, length: int):
        """The tier's one match slab (``models/slab.py``), on the device."""
        slab = np.zeros(match_slab_shape(rows, width, h), np.uint8)
        data, lengths, vdata, vlengths = match_views(slab)
        data[:, :length] = rng.integers(0x20, 0x7F, (rows, length), dtype=np.uint8)
        lengths[:] = length
        vdata[:] = data
        vlengths[:] = length
        return (jax.device_put(slab),)

    def compile_one(shape: str):
        rows, width = map(int, shape.split("x"))
        t0 = time.perf_counter()
        fn = stage_executable("match", shape)
        compiled = fn.lower(model, *operands(rows, width, width), mask=None).compile()
        return shape, compiled, time.perf_counter() - t0

    out = []
    with ThreadPoolExecutor(max_workers=len(args.shapes)) as pool:
        for shape, compiled, compile_s in pool.map(compile_one, args.shapes):
            rows, width = map(int, shape.split("x"))
            line = {"shape": shape, "rows": rows, "width": width, "trace_and_compile_s": compile_s}
            for name, length in (("full_rows", width), ("rows_of_32_bytes", min(32, width))):
                ops = operands(rows, width, length)
                for _ in range(3):
                    jax.block_until_ready(compiled(model, *ops))
                ms = []
                for _ in range(args.calls):
                    t0 = time.perf_counter()
                    jax.block_until_ready(compiled(model, *ops))
                    ms.append(1e3 * (time.perf_counter() - t0))
                line[name + "_ms"] = {"min": min(ms), "median": statistics.median(ms),
                                      "max": max(ms)}
            out.append(line)
            print(json.dumps(line), flush=True)
    dest = REPO / "chiprun_out"
    dest.mkdir(exist_ok=True)
    (dest / "matcher_shape_probe.json").write_text(json.dumps(out, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
