"""How a rule set's matcher executable's time moves with rows and width.

    python3 hack/matcher_shape_probe.py 8x512 64x512 8x2048 64x2048 [--rules DIR]

On the device JAX finds (a TPU through the builder's chip tool; the CPU
cannot compile full crs-lite): builds the engine, compiles
``jit_cko_match_<rows>x<width>`` for each shape (in parallel: XLA
releases the interpreter lock), then calls each 20 times with the
model's tables resident and waits for the result, twice: every row as
long as the tier is wide, and every row 32 bytes long in the same tier;
``memory`` is what the executable holds beside its operands
(``memory_analysis``) and the device's peak with two launches in flight.
Wall time per call is device time here: nothing else runs, and a call
is tens of milliseconds against tens of microseconds of dispatch. The
second line says what a call launches (``automata_summary()``: flat
bins, their slots and groups, blocks left on one kernel a bank). One
JSON line per shape, all of them again in
``chiprun_out/matcher_shape_probe.json``. ROADMAP Speed 2's question
("fixed, or grows with rows?") is answered by the lines it prints.

``--scopes`` prices each shape by device scope
(``observability/device_scopes.py``): after the timed calls, which stay
untraced, one ``jax.profiler`` capture (Python tracer off, no HLO protos,
as the sidecar's ``/waf/v1/profile`` takes it) of ``--scope-calls`` more
calls on full rows, reduced with ``reduce_by_scope`` against the
executable's own table. The shape's line gains ``device_ops`` (what a
launch is made of, static), ``scopes`` (per scope: ms a call, operations
run a call, us an operation), ``dearest_structures`` (the ten suffix
structures with most time) and ``capture`` (calls, the executable's
``XLA Modules`` ms a call beside the operations' sum, how the events were
joined, the stats an operation's event carries, the ten dearest
instructions); the whole reduction goes to
``chiprun_out/matcher_scopes_<shape>.json``.

``--opcodes cko.seg.embed,cko.seg.nce`` (with ``--scopes``) splits each
named scope by what its operations are: the HLO opcode, and for a fusion
the opcodes its computation holds that are not elementwise
(``fusion(reduce-window)``, ``fusion(dot)``; ``fusion`` alone is
comparisons and selects). Per kind the static count, and from the capture
ms a call and operations run a call; ``reduce_window_instructions``
counts every ``reduce-window`` of the optimized HLO by scope, inside
fusions too.

With ``--scopes`` every line also carries ``convolutions``: for each
convolution of ``cko.seg.conv`` (an instruction of the optimized HLO, or
the fusion that holds it) the block it belongs to, its rows x positions x
columns, the taps it was traced with beside the block's W and C, ms a call
from the capture, and its useful flops (2·T·Q·W·C·N2: what the plain conv
needs, zero taps and all) over that time as a share of
``wafbench/peaks.json``'s ``bf16_flops_per_s``; ``seg_plan`` beside them
has the static reading (``conv_passes``, ``conv_fill``:
``models/waf_model.py:SegTierPlan``). ``--plain-conv`` traces the conv one
tap a contraction (``ops/segment.py:conv_tap_packing`` patched to 1: the
program until PR 47), to price the packing against it in one call.
"""

from __future__ import annotations

import argparse
import json
import os
import re
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
    ap.add_argument("--scopes", action="store_true", help="price each shape by device scope")
    ap.add_argument("--scope-calls", type=int, default=8, help="calls in the --scopes capture")
    ap.add_argument("--opcodes", default="", help="scopes to split by opcode, comma-separated")
    ap.add_argument("--plain-conv", action="store_true", help="one tap a contraction, as until PR 47")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.compile_cache import configure_persistent_cache
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.models.slab import match_slab_shape, match_views
    from coraza_kubernetes_operator_tpu.models.waf_model import stage_executable, tier_seg_plan
    from coraza_kubernetes_operator_tpu.observability import device_scopes
    from coraza_kubernetes_operator_tpu.ops import segment
    from wafbench.harness import read_rules

    if args.plain_conv:
        segment.conv_tap_packing = lambda spec: (1, spec.w)

    # Where JAX_COMPILATION_CACHE_DIR holds a cache, its keys carry the scopes' salt:
    # an executable another build cached would come back with that build's names.
    configure_persistent_cache()
    dev = jax.devices()[0]
    peaks = json.loads((REPO / "wafbench/peaks.json").read_text())["peaks"].get(dev.device_kind, {})
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

    dest = REPO / "chiprun_out"
    dest.mkdir(exist_ok=True)

    # What a fusion is named for: the opcodes in it that are not one pass of the VPU.
    heavy = ("reduce-window", "dot", "convolution", "reduce", "concatenate", "pad", "gather",
             "scatter", "dynamic-slice", "dynamic-update-slice", "sort", "transpose", "copy")

    def by_opcode(text: str, events: dict, names: dict, calls: int) -> dict:
        """``--opcodes``: each named scope's operations by kind."""
        _entry, comps = device_scopes._parse(text)
        kind, inside = {}, {}
        for body in comps.values():
            for i in body:
                kind[i.name] = i.opcode
                if i.fused:
                    held = [j.opcode for j in comps.get(i.fused, ())]
                    kind[i.name] = "fusion(" + ",".join(h for h in heavy if h in held) + ")"
                    inside[i.name] = held.count("reduce-window")
        reduce_windows: dict[str, int] = {}
        for instr, path in names.items():
            n = inside.get(instr, 0) + (kind.get(instr) == "reduce-window")
            if n:
                scope = device_scopes.scope_of(path)
                reduce_windows[scope] = reduce_windows.get(scope, 0) + n
        out = {"reduce_window_instructions": reduce_windows}
        for scope in filter(None, args.opcodes.split(",")):
            rows: dict[str, list] = {}  # kind -> [static, seconds, operations run]
            for instr, path in names.items():
                if device_scopes.scope_of(path) == scope:
                    rows.setdefault(kind.get(instr, "?"), [0, 0.0, 0])[0] += 1
            for dev in events["devices"]:
                run = sorted(dev["ops"], key=lambda e: (e[1], -e[2]))
                for e, self_ns in zip(run, device_scopes._self_ns(run)):
                    instr = device_scopes.instruction_name(e[0])
                    if device_scopes.scope_of(names.get(instr, "")) == scope:
                        row = rows.setdefault(kind.get(instr, "?"), [0, 0.0, 0])
                        row[1] += self_ns / 1e9
                        row[2] += 1
            out[scope] = {k: {"static": n, "ms_per_call": 1e3 * sec / calls, "ops_per_call": ran / calls}
                          for k, (n, sec, ran) in sorted(rows.items(), key=lambda kv: -kv[1][1])}
        return out

    convolution = re.compile(r"=\s+\w+\[([\d,]+)\]\S*\s+convolution\(.*window=\{size=(\d+).*dim_labels=\w+_\w+->(\w+)")

    def convolutions(text: str, events: dict, names: dict, calls: int, plan) -> list[dict]:
        """``cko.seg.conv``'s convolutions against the chip's bf16 peak."""
        _entry, comps = device_scopes._parse(text)
        held, comp = {}, None  # computation or instruction -> (T, Q, N2, taps)
        for line in text.splitlines():
            started = device_scopes._COMPUTATION.match(line)
            if started:
                comp = started.group(2)
            found = convolution.search(line)
            if found:
                dims = dict(zip(found.group(3), map(int, found.group(1).split(","))))
                held[comp] = held[device_scopes._INSTRUCTION.match(line).group(2)] = (
                    dims["b"], dims["0"], dims["f"], int(found.group(2)))
        convs = {i.name: held.get(i.fused) or held[i.name] for body in comps.values() for i in body
                 if (i.fused in held or i.opcode == "convolution")
                 and device_scopes.scope_of(names.get(i.name, "")) == "cko.seg.conv"}
        seconds = dict.fromkeys(convs, 0.0)
        for dev_events in events["devices"]:
            run = sorted(dev_events["ops"], key=lambda e: (e[1], -e[2]))
            for e, self_ns in zip(run, device_scopes._self_ns(run)):
                instr = device_scopes.instruction_name(e[0])
                if instr in seconds:
                    seconds[instr] += self_ns / 1e9
        specs = [sb.spec for sb in engine.model.segs]
        out = []
        for instr, (t, q, n2, taps) in convs.items():
            # A tile's conv has its columns; two blocks of as many columns differ by their taps.
            block = next((i for i, _g0, _g1, c in plan.tiles
                          if c == n2 and taps in (specs[i].w, segment.conv_tap_packing(specs[i])[1])), None)
            line = {"instruction": instr, "block": block, "rows": t, "positions": q, "columns": n2,
                    "taps": taps, "ms_per_call": 1e3 * seconds[instr] / calls}
            if block is not None:
                w, c = specs[block].w, len(specs[block].channels)
                line.update(w=w, c=c, useful_flops=2 * t * q * w * c * n2)
                if seconds[instr] and peaks:
                    line["share_of_bf16_peak"] = (line["useful_flops"] * calls / seconds[instr]
                                                  / peaks["bf16_flops_per_s"])
            out.append(line)
        return sorted(out, key=lambda line: -line["ms_per_call"])

    def priced(shape: str, compiled, ops) -> dict:
        """One capture of ``--scope-calls`` calls, reduced by scope."""
        t0 = time.perf_counter()
        text = compiled.as_text()
        t1 = time.perf_counter()
        names, inherited = device_scopes.walk(text)
        static = device_scopes.counts(names, inherited)
        walk_s = time.perf_counter() - t1
        trace_dir = REPO / "build" / f"matcher_scopes_trace_{shape}"  # a capture is tens of MB: not chiprun_out
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        for _ in range(args.scope_calls):
            jax.block_until_ready(compiled(model, *ops))
        t2 = time.perf_counter()
        jax.profiler.stop_trace()
        stop_s = time.perf_counter() - t2
        events = device_scopes.extract(trace_dir)
        name = f"cko_match_{shape}"
        reduced = device_scopes.reduce_by_scope(events, {name: names}).get(name)
        (dest / f"matcher_scopes_{shape}.json").write_text(
            json.dumps({"device_ops": static, "op_stats": events["op_stats"], "reduced": reduced}))
        gained = {"device_ops": static, "as_text_s": t1 - t0, "walk_s": walk_s, "text_bytes": len(text)}
        if args.opcodes:
            gained["opcodes"] = by_opcode(text, events, names, reduced["runs"] if reduced else 1)
        rows, width = map(int, shape.split("x"))
        plan = tier_seg_plan(engine.model, rows, width)
        if plan:
            gained["seg_plan"] = plan.summary()
            gained["convolutions"] = convolutions(text, events, names, reduced["runs"] if reduced else 1, plan)
        if not reduced:  # the CPU has no device plane
            return dict(gained, capture={"calls": 0, "stop_s": stop_s})
        calls = reduced["runs"]
        gained["scopes"] = {
            scope: {"ms_per_call": 1e3 * cell["s"] / calls, "ops_per_call": cell["ops"] / calls,
                    "us_per_op": 1e6 * cell["s"] / cell["ops"]}
            for scope, cell in sorted(device_scopes.by_registry_scope(reduced["scopes"]).items(),
                                      key=lambda kv: -kv[1]["s"])}
        gained["dearest_structures"] = [
            [path, 1e3 * sec / calls, n / calls]
            for path, sec, n in device_scopes.dearest_beneath(reduced["scopes"], "cko.seg.suffix")]
        gained["capture"] = {
            "calls": calls, "stop_s": stop_s,
            "module_ms_per_call": 1e3 * reduced["module_s"] / calls,
            "ops_ms_per_call": 1e3 * reduced["ops_s"] / calls,
            "joined_by": reduced["joined_by"], "op_stats": events["op_stats"],
            "dearest_ops": [[instr, path, 1e3 * sec / calls] for instr, path, sec in reduced["dearest"]]}
        return gained

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
            # What a launch holds on the device: the executable's own temporaries
            # (static), and the process's peak after two launches were in flight at
            # once, as two lanes hold them (it only grows: put the small shape first).
            pair = [compiled(model, *ops) for _ in range(2)]
            jax.block_until_ready(pair)
            del pair
            held, stats = compiled.memory_analysis(), dev.memory_stats() or {}
            line["memory"] = {
                "temp_bytes": held.temp_size_in_bytes, "argument_bytes": held.argument_size_in_bytes,
                "output_bytes": held.output_size_in_bytes,
                **{k: stats.get(k) for k in ("bytes_limit", "peak_bytes_in_use", "bytes_in_use")}}
            if args.scopes:
                line.update(priced(shape, compiled, operands(rows, width, width)))
            out.append(line)
            print(json.dumps(line), flush=True)
    (dest / "matcher_shape_probe.json").write_text(json.dumps(out, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
