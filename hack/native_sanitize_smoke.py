#!/usr/bin/env python
"""ASan/UBSan gate for the native fast path (`make native.sanitize`,
CI job native-sanitize, docs/NATIVE.md "Sanitizer gate").

The parity smoke (hack/native_parity_smoke.py) proves the native window
pipeline computes the RIGHT answers; this gate proves it computes them
SAFELY. The same deterministic corpus plus a seeded blob-bounds fuzzer
(hack/native_fuzz_seeds.json) is replayed twice in child processes:

  1. against the regular ``libcko_native.so``;
  2. against ``libcko_native.asan.so`` (``make -C native asan``), with
     libasan LD_PRELOADed into the non-instrumented Python.

Pass requires all of:
  - both children exit 0 with ZERO ASan/UBSan reports
    (-fno-sanitize-recover turns any report into a crash);
  - the children's verdict/tensor digests are BIT-IDENTICAL — the
    sanitized build must not change behavior;
  - every seeded mutation survives the raw ABI (cko_tensorize /
    cko_blob_overlimit / cko_json_to_blob return NULL / a count /
    nullptr instead of reading out of bounds), with lying ``n_req``
    values layered on top;
  - forced ``cko_result_export`` / ``cko_plan_export`` overflows return
    a negative rc, and a clean window exported into the SAME buffers
    afterwards digests identically to a fresh-buffer export — a failed
    export never leaves residue the next window can observe;
  - the prefilter confirm ABI (``cko_confirm_new`` / ``cko_confirm_run``)
    refuses malformed table blobs with NULL, walks rows exactly ``L``
    long, zero-length rows and positives on the last row inside their
    buffers, and answers a row, group, plane or length out of range with
    a negative rc.

Skips LOUDLY (exit 0) when the sanitized library or libasan is missing.
Env knobs: CKO_SANITIZE_SEED / CKO_SANITIZE_ITERS / CKO_SANITIZE_WINDOWS.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

SEED = int(os.environ.get("CKO_SANITIZE_SEED", "0"))
ITERS = int(os.environ.get("CKO_SANITIZE_ITERS", "120"))
WINDOWS = int(os.environ.get("CKO_SANITIZE_WINDOWS", "4"))
WINDOW = 64

REGULAR_LIB = REPO / "native" / "libcko_native.so"
ASAN_LIB = REPO / "native" / "libcko_native.asan.so"
SEEDS_PATH = REPO / "hack" / "native_fuzz_seeds.json"


# ---------------------------------------------------------------------------
# Seeded mutations
# ---------------------------------------------------------------------------


def _mutate(blob: bytes, spec: dict) -> bytes:
    op = spec["op"]
    n = len(blob)
    if op == "truncate_frac":
        return blob[: max(0, int(n * spec["frac"]))]
    if op == "truncate_bytes":
        return blob[: max(0, n - spec["n"])]
    if op == "truncate_bytes_to":
        return (blob + b"\x00" * spec["n"])[: spec["n"]]
    if op == "patch_u32":
        off = min(max(0, int(n * spec["frac"])), max(0, n - 4))
        v = int(spec["value"]).to_bytes(4, "little")
        return blob[:off] + v + blob[off + 4 :]
    if op == "zero_range":
        off = min(max(0, int(n * spec["frac"])), n)
        k = min(spec["n"], n - off)
        return blob[:off] + b"\x00" * k + blob[off + k :]
    if op == "bitflip_stride":
        out = bytearray(blob)
        for i in range(0, n, spec["stride"]):
            out[i] ^= 0x80
        return bytes(out)
    if op == "append_bytes":
        return blob + bytes([spec["byte"]]) * spec["n"]
    if op == "append_u32":
        return blob + int(spec["value"]).to_bytes(4, "little")
    raise ValueError(f"unknown mutation op {op!r}")


# ---------------------------------------------------------------------------
# Child: replay everything against whichever library CKO_NATIVE_LIB picked,
# print one deterministic JSON digest line.
# ---------------------------------------------------------------------------


def _digest(h: "hashlib._Hash") -> str:
    return h.hexdigest()


def _hash_arrays(h, arrays) -> None:
    import numpy as np

    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())


def _corpus_digests(out: dict) -> None:
    """Verdict + tensor digests over the deterministic parity corpus."""
    # The parity smoke reads its knobs at import time — set them first.
    os.environ["CKO_PARITY_SEED"] = str(SEED)
    os.environ["CKO_PARITY_ITERS"] = str(ITERS)
    from coraza_kubernetes_operator_tpu.corpus import sample_rules
    from coraza_kubernetes_operator_tpu.engine import WafEngine
    from coraza_kubernetes_operator_tpu.native import serialize_requests
    from hack.native_parity_smoke import _verdict_key, fuzz_requests
    engine = WafEngine(sample_rules())
    out["tiered"] = engine._native.tiered
    reqs = fuzz_requests()

    vh = hashlib.sha256()
    th = hashlib.sha256()
    windows = 0
    for off in range(0, min(len(reqs), WINDOWS * WINDOW), WINDOW):
        win = reqs[off : off + WINDOW]
        blob = serialize_requests(win)
        if engine._native.tiered:
            tiers, numvals, masks, cached, miss, lease = engine._native.tier_blob(
                blob, len(win), engine._kind_block_lut, engine.value_cache
            )
            try:
                th.update(repr(masks).encode())
                for tier in tiers:
                    _hash_arrays(th, tier)
                _hash_arrays(th, [numvals])
                for c in cached or ():
                    _hash_arrays(th, [c])
                for tier_keys in miss or ():
                    for k in tier_keys:
                        th.update(bytes(k))
            finally:
                lease.release()
        for v in engine.collect(engine.prepare_blob(blob, len(win))):
            vh.update(repr(_verdict_key(v)).encode())
        windows += 1
    out["windows"] = windows
    out["verdicts"] = _digest(vh)
    out["tensors"] = _digest(th)


def _fuzz_bounds(out: dict) -> None:
    """Seeded mutations against the raw ABI: survival is the assertion —
    any out-of-bounds access dies under ASan; classification counts are
    digest material so both libraries must also AGREE on every outcome."""
    from coraza_kubernetes_operator_tpu.corpus import sample_rules
    from coraza_kubernetes_operator_tpu.engine import WafEngine
    from coraza_kubernetes_operator_tpu.native import (
        load_library,
        serialize_requests,
    )
    from hack.native_parity_smoke import fuzz_requests

    lib = load_library()
    seeds = json.loads(SEEDS_PATH.read_text())
    engine = WafEngine(sample_rules())
    ctx = engine._native._ctx
    assert ctx is not None

    base_reqs = fuzz_requests()[:WINDOW]
    base_blob = serialize_requests(base_reqs)
    n_req = len(base_reqs)

    outcomes: list[str] = []
    for spec in seeds["blob_mutations"]:
        mut = _mutate(base_blob, spec)
        marks = []
        # cko_blob_overlimit with a deliberately tiny out array: the
        # found-count may exceed max_out, writes must not.
        max_out = 2
        idx = (ctypes.c_int32 * max_out)()
        found = lib.cko_blob_overlimit(mut, len(mut), 8, idx, max_out)
        marks.append(f"ovl={found}")
        # cko_tensorize under every lying n_req, then a correct-ish one.
        for lie in [*seeds["nreq_lies"], n_req]:
            res = lib.cko_tensorize(ctx, mut, len(mut), lie)
            if res:
                rows = lib.cko_result_rows(res)
                lib.cko_result_free(res)
                marks.append(f"n{lie}=rows:{rows}")
            else:
                marks.append(f"n{lie}=null")
        outcomes.append(spec["name"] + "(" + ",".join(marks) + ")")
    for i, payload in enumerate(seeds["json_payloads"]):
        body = payload.encode()
        h = lib.cko_json_to_blob(body, len(body))
        if h:
            nreq = lib.cko_blob_nreq(h)
            lib.cko_blob_free(h)
            outcomes.append(f"json{i}=nreq:{nreq}")
        else:
            outcomes.append(f"json{i}=null")
    out["fuzz_cases"] = len(outcomes)
    out["fuzz"] = _digest(hashlib.sha256("|".join(outcomes).encode()))


def _export_overflow(out: dict) -> None:
    """Force the negative-rc export paths, then prove a clean window
    exported into the SAME buffers matches a fresh-buffer export."""
    import numpy as np

    from coraza_kubernetes_operator_tpu.corpus import sample_rules
    from coraza_kubernetes_operator_tpu.engine import WafEngine
    from coraza_kubernetes_operator_tpu.native import (
        load_library,
        serialize_requests,
    )
    from hack.native_parity_smoke import fuzz_requests

    lib = load_library()
    engine = WafEngine(sample_rules())
    ctx = engine._native._ctx
    reqs = fuzz_requests()[:WINDOW]
    blob = serialize_requests(reqs)
    n = len(reqs)

    res = lib.cko_tensorize(ctx, blob, len(blob), n)
    assert res, "valid blob must tensorize"
    try:
        rows = lib.cko_result_rows(res)
        maxlen = lib.cko_result_maxlen(res)
        T, L = rows, max(maxlen, 1)
        H = getattr(engine._native, "_n_host", 0) or 1
        NV = max(getattr(engine._native, "_nv", 0), 1)

        def alloc():
            return dict(
                data=np.zeros((T, L), dtype=np.uint8),
                lengths=np.zeros(T, dtype=np.int32),
                k1=np.zeros(T, dtype=np.int32),
                k2=np.zeros(T, dtype=np.int32),
                k3=np.zeros(T, dtype=np.int32),
                req_id=np.zeros(T, dtype=np.int32),
                vdata=np.zeros((H, T, L), dtype=np.uint8),
                vlengths=np.zeros((H, T), dtype=np.int32),
                numvals=np.zeros((n, NV), dtype=np.int32),
            )

        def ptr(a):
            return a.ctypes.data_as(ctypes.c_void_p)

        def export(bufs, t, length):
            return lib.cko_result_export(
                res,
                ptr(bufs["data"]), ptr(bufs["lengths"]), ptr(bufs["k1"]),
                ptr(bufs["k2"]), ptr(bufs["k3"]), ptr(bufs["req_id"]),
                ptr(bufs["vdata"]), ptr(bufs["vlengths"]), ptr(bufs["numvals"]),
                t, length, H, n, NV, n,
            )

        rcs = []
        reused = alloc()
        if rows > 0:
            # Undersized row bucket -> rc -1 before anything is written.
            # (The export checks rows > T up front, so passing a smaller T
            # with matching buffers is safe even under ASan.)
            small = dict(reused)
            rcs.append(export(small, rows - 1, L))
            if maxlen > 0:
                # Row bucket fits but a value exceeds the length bucket ->
                # rc -2 after some rows were already scattered: exactly the
                # partial-write case the reuse check below proves harmless.
                # Buffer extents stay T x L so no OOB even mid-loop.
                rcs.append(export(reused, T, 0))
        out["export_rcs"] = rcs
        assert all(rc < 0 for rc in rcs), f"forced overflow rcs: {rcs}"

        # Clean export into the dirty reused buffers vs fresh ones.
        fresh = alloc()
        rc1 = export(reused, T, L)
        rc2 = export(fresh, T, L)
        assert rc1 == 0 and rc2 == 0, (rc1, rc2)
        for k in fresh:
            assert np.array_equal(reused[k], fresh[k]), (
                f"residue after failed export leaked into {k}"
            )
        h = hashlib.sha256()
        _hash_arrays(h, [fresh[k] for k in sorted(fresh)])
        out["export"] = _digest(h)
    finally:
        lib.cko_result_free(res)

    # Plan-path overflow: tier_blob with a corrupted-bounds forced small
    # arena is exercised indirectly — cko_plan_keys with a bad tier index
    # must rc -1 without writing.
    if engine._native.tiered:
        from coraza_kubernetes_operator_tpu.native import _BOUNDS_ARR

        plan = lib.cko_plan_new(
            ctx, blob, len(blob), n, ptr_arr(_BOUNDS_ARR), len(_BOUNDS_ARR),
            4, None, 0, 1, 0, 1,
        )
        if plan:
            scratch = (ctypes.c_uint8 * 8)()
            rc_bad = lib.cko_plan_keys(plan, 10_000, scratch)
            rc_neg = lib.cko_plan_keys(plan, -1, scratch)
            out["plan_keys_rcs"] = [rc_bad, rc_neg]
            assert rc_bad == -1 and rc_neg == -1
            lib.cko_plan_free(plan)


# Prefiltered groups for the confirm fuzz: the 384-state pattern of
# tests/test_automata_routing.py under a device pipeline (raw row +
# native transforms) and a host one (variant plane).
CONFIRM_RULES = """
SecRuleEngine On
SecRule ARGS "@rx (a|bc)*a(a|bc){7}d" "id:1,phase:2,deny,status:403,t:none,t:urlDecodeUni,t:htmlEntityDecode"
SecRule ARGS "@rx (a|bc)*a(a|bc){7}d" "id:2,phase:2,deny,status:403,t:none,t:lowercase"
SecRule ARGS "@rx (a|bc)*a(a|bc){7}d" "id:3,phase:2,deny,status:403,t:none,t:cmdLine"
"""


def _confirm_bounds(out: dict) -> None:
    """Seeded bounds fuzz of the prefilter confirm ABI: malformed table
    blobs must come back NULL; calls with rows exactly ``L`` long,
    zero-length rows and positives on the last row must stay inside
    their buffers (ASan watches) and agree between builds; a row, group,
    plane or length out of range must return a negative rc."""
    import random

    import numpy as np

    from coraza_kubernetes_operator_tpu.engine import WafEngine
    from coraza_kubernetes_operator_tpu.native import (
        load_library,
        serialize_confirm,
    )

    lib = load_library()
    if not getattr(lib, "_cko_has_confirm", False):
        out["confirm"] = "absent"
        return
    os.environ["CKO_AUTOMATA"] = "1"
    engine = WafEngine(CONFIRM_RULES)
    nc = engine._native_confirm
    n_groups = len(engine.model.prefilter_cols)
    assert n_groups == 3 and nc.handled.all(), (n_groups, nc.handled)
    slots = [
        int(engine.model.host_variant_index[engine.compiled.group_pipeline[g]])
        for _c, g in engine.model.prefilter_cols
    ]
    assert min(slots) == -1 and max(slots) >= 0, slots
    blob, _handled, _group_of = serialize_confirm(
        engine.compiled, engine.model.prefilter_cols, engine.model.host_variant_index
    )

    rng = random.Random(SEED)
    marks = []
    # Malformed table blobs: truncations, lying counts, a next-state past S.
    for i in range(48):
        cut = rng.randrange(0, len(blob))
        mut = bytearray(blob[:cut] if i % 3 == 0 else blob)
        if i % 3 == 1:
            off = rng.randrange(0, 64)
            mut[off : off + 4] = rng.choice([0xFFFFFFFF, 0x7FFFFFFF, 1 << 20]).to_bytes(4, "little")
        if i % 3 == 2:
            off = len(blob) - 4 * rng.randrange(600, 60_000)
            mut[off : off + 4] = (0x7FFFFFFF).to_bytes(4, "little")
        h = lib.cko_confirm_new(bytes(mut), len(mut))
        marks.append("null" if not h else "ok")
        if h:
            lib.cko_confirm_free(h)
    assert marks.count("null") >= 40, marks

    u, width = 9, 64
    h_planes = max(1, max(slots) + 1)
    alphabet = b"abcd%&#;x3Cu0 "

    def tier():
        data = np.zeros((u, width), dtype=np.uint8)
        lengths = np.zeros(u, dtype=np.int32)
        vdata = np.zeros((h_planes, u, width), dtype=np.uint8)
        vlengths = np.zeros((h_planes, u), dtype=np.int32)
        for i in range(u):
            # Row 0 stays empty, the last row is exactly `width` long.
            n = 0 if i == 0 else width if i == u - 1 else rng.randrange(0, width + 1)
            data[i, :n] = rng.choices(alphabet, k=n)
            lengths[i] = n
            for hp in range(h_planes):
                m = width if i == u - 1 else rng.randrange(0, width + 1)
                vdata[hp, i, :m] = rng.choices(alphabet, k=m)
                vlengths[hp, i] = m
        return (data, lengths, None, None, None, None, vdata, vlengths)

    def ptr(a):
        return a.ctypes.data_as(ctypes.c_void_p)

    def raw(t, rows, groups, n_planes=h_planes):
        rows = np.asarray(rows, dtype=np.int32)
        groups = np.asarray(groups, dtype=np.int32)
        res = np.zeros(max(1, rows.size), dtype=np.uint8)
        rc = lib.cko_confirm_run(
            nc._h, ptr(t[0]), ptr(t[1]), u, width, ptr(t[6]), ptr(t[7]),
            n_planes, ptr(rows), ptr(groups), rows.size, ptr(res),
        )
        return rc, res[: rows.size]

    digest = hashlib.sha256()
    for _ in range(ITERS):
        t = tier()
        # Every (row, group), the last row and the empty one included.
        rows = np.repeat(np.arange(u), n_groups)
        groups = np.tile(np.arange(n_groups), u)
        res = nc.run(t, rows, groups)
        digest.update(res.tobytes())
        rc0, _ = raw(t, [], [])
        bad = [
            raw(t, [u], [0])[0],  # one past the last row
            raw(t, [-1], [0])[0],
            raw(t, [u - 1], [n_groups])[0],
            raw(t, [0], [-1])[0],
            raw(t, [0], [slots.index(max(slots))], n_planes=0)[0],  # no such plane
        ]
        t[1][u - 1] = width + 1  # a length one past the row
        t[7][:, u - 1] = width + 1
        bad += [raw(t, [u - 1], [k])[0] for k in range(n_groups)]
        t[1][0] = -1
        bad.append(raw(t, [0], [slots.index(-1)])[0])
        assert rc0 == 0 and all(rc < 0 for rc in bad), (rc0, bad)
        digest.update(repr(bad).encode())
    out["confirm_blobs"] = marks
    out["confirm"] = _digest(digest)


def ptr_arr(a):
    import numpy as np

    return np.ascontiguousarray(a).ctypes.data_as(ctypes.c_void_p)


def child() -> int:
    out: dict = {"lib": os.environ.get("CKO_NATIVE_LIB", "default")}
    _corpus_digests(out)
    _fuzz_bounds(out)
    _export_overflow(out)
    _confirm_bounds(out)
    print("SANITIZE-DIGEST " + json.dumps(out, sort_keys=True))
    return 0


# ---------------------------------------------------------------------------
# Parent: run the child against both libraries, diff the digests.
# ---------------------------------------------------------------------------


def _gcc_lib(name: str) -> str | None:
    try:
        p = subprocess.run(
            ["g++", f"-print-file-name={name}"],
            capture_output=True, text=True, timeout=30,
        ).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return None
    return p if p and Path(p).is_file() else None


def _run_child(env_extra: dict) -> tuple[int, str, str]:
    env = dict(os.environ)
    env.update(env_extra)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, __file__, "--child"],
        capture_output=True, text=True, env=env, cwd=str(REPO), timeout=1800,
    )
    return proc.returncode, proc.stdout, proc.stderr


def _extract_digest(stdout: str) -> dict | None:
    for line in stdout.splitlines():
        if line.startswith("SANITIZE-DIGEST "):
            d = json.loads(line[len("SANITIZE-DIGEST "):])
            d.pop("lib", None)
            return d
    return None


def main() -> int:
    if "--child" in sys.argv:
        return child()

    if not REGULAR_LIB.exists() or not ASAN_LIB.exists():
        print(
            "native-sanitize SKIP: build both libraries first "
            "(make -C native all asan)"
        )
        return 0
    libasan = _gcc_lib("libasan.so")
    if libasan is None:
        print("native-sanitize SKIP: libasan.so not found (need g++ toolchain)")
        return 0
    # libstdc++ must ride along: jaxlib's pybind modules import
    # __cxa_throw dynamically, and ASan's interceptor aborts ("CHECK
    # failed ... real___cxa_throw != 0") unless the real symbol is
    # already resolvable at libasan init.
    libstdcpp = _gcc_lib("libstdc++.so")
    preload = " ".join(p for p in (libasan, libstdcpp) if p)

    rc_reg, out_reg, err_reg = _run_child({"CKO_NATIVE_LIB": str(REGULAR_LIB)})
    if rc_reg != 0:
        print("native-sanitize FAIL: regular-lib child failed")
        print(out_reg[-2000:])
        print(err_reg[-2000:])
        return 1

    rc_asan, out_asan, err_asan = _run_child({
        "CKO_NATIVE_LIB": str(ASAN_LIB),
        "LD_PRELOAD": preload,
        # Python itself is not instrumented: leak detection would drown in
        # interpreter-lifetime allocations; every other check stays on and
        # any report aborts (the .so is built -fno-sanitize-recover).
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1",
        "UBSAN_OPTIONS": "halt_on_error=1:print_stacktrace=1",
    })
    sanitizer_noise = [
        ln for ln in (err_asan or "").splitlines()
        if "ERROR: AddressSanitizer" in ln
        or "runtime error:" in ln
        or "AddressSanitizer CHECK failed" in ln
    ]
    if rc_asan != 0 or sanitizer_noise:
        print("native-sanitize FAIL: sanitizer report or asan child failure")
        for ln in sanitizer_noise[:10]:
            print("  " + ln)
        print(out_asan[-2000:])
        # The report's head names the bug class and faulting frame; the
        # tail is usually interpreter boilerplate. Print head-first.
        print(err_asan[:4000])
        if len(err_asan) > 4000:
            print(f"... [{len(err_asan) - 4000} bytes elided]")
        return 1

    d_reg = _extract_digest(out_reg)
    d_asan = _extract_digest(out_asan)
    if d_reg is None or d_asan is None:
        print("native-sanitize FAIL: missing digest line")
        print(out_reg[-1000:])
        print(out_asan[-1000:])
        return 1
    if d_reg != d_asan:
        print("native-sanitize FAIL: digests diverge between builds")
        for k in sorted(set(d_reg) | set(d_asan)):
            a, b = d_reg.get(k), d_asan.get(k)
            if a != b:
                print(f"  {k}: regular={a} asan={b}")
        return 1

    print(
        "native-sanitize PASS "
        + json.dumps(
            {
                "windows": d_reg.get("windows"),
                "fuzz_cases": d_reg.get("fuzz_cases"),
                "export_rcs": d_reg.get("export_rcs"),
                "confirm": (d_reg.get("confirm") or "")[:12],
                "verdicts": (d_reg.get("verdicts") or "")[:12],
                "tensors": (d_reg.get("tensors") or "")[:12],
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
