"""The native prefilter confirm against its oracle, and its fallbacks.

``WafEngine._confirm_prefilter`` hands a tier's device prefilter
positives to ``cko_confirm_run`` in one call where the native library
handles the group, and to ``_confirm_python`` (``DFA.search`` over
``apply_pipeline``) otherwise. Both must clear exactly the same bits.

The library comes from ``conftest.py``'s ``native_lib`` (built from the
committed source into a temp dir, loaded through ``CKO_NATIVE_LIB``), so
these cases run wherever a C++ compiler exists, not only where somebody
ran ``make native`` first. The load is undone after each fixture or
test.

No device executable is involved: the tiers and the packed hit rows are
made here, which is also what lets full crs-lite take part on the CPU.
"""

import base64
import random
from pathlib import Path

import numpy as np
import pytest

import coraza_kubernetes_operator_tpu.native as native
from coraza_kubernetes_operator_tpu.compiler.ruleset import (
    compile_rules,
    compile_rules_cached,
)
from coraza_kubernetes_operator_tpu.compiler.transforms_host import apply_pipeline
from coraza_kubernetes_operator_tpu.engine import WafEngine
from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text
from coraza_kubernetes_operator_tpu.observability.stages import current as current_stages

from conftest import dfa_witness as _witness, load_native, native_engine

CRS_CACHE_DIR = str(Path(__file__).resolve().parent / ".crs_cache")

# crs-lite's prefiltered groups (docs/AUTOMATA.md; the wafbench cell's 11).
N_CRS_LITE_GROUPS = 11

# One synthetic rule per native transform opcode, all over the 384-state
# pattern tests/test_automata_routing.py prefilters. Pipelines the device
# cannot run get a host variant plane, so both of the call's sources of
# bytes (raw row + native transform, variant row) are walked.
BIG = r"(a|bc)*a(a|bc){7}d"
WITNESS = b"xxaaaaaaaadxx"
NATIVE_TRANSFORMS = [
    "none", "lowercase", "uppercase", "urlDecode", "urlDecodeUni", "urlEncode",
    "htmlEntityDecode", "removeNulls", "replaceNulls", "removeWhitespace",
    "compressWhitespace", "trim", "trimLeft", "trimRight", "removeComments",
    "removeCommentsChar", "replaceComments", "normalizePath",
    "normalizePathWin", "cmdLine", "jsDecode", "cssDecode", "base64Decode",
    "base64DecodeExt", "base64Encode", "hexDecode", "hexEncode",
    "escapeSeqDecode", "utf8toUnicode", "length",
]
SYNTHETIC_RULES = "SecRuleEngine On\n" + "".join(
    f'SecRule ARGS "@rx {BIG}" "id:{1000 + i},phase:2,deny,status:403,t:none,t:{t}"\n'
    for i, t in enumerate(NATIVE_TRANSFORMS)
) + (
    # Two-op pipelines as crs-lite has them, and one op the library lacks.
    f'SecRule ARGS "@rx {BIG}" "id:1100,phase:2,deny,status:403,t:none,t:urlDecodeUni,t:htmlEntityDecode"\n'
    f'SecRule ARGS "@rx {BIG}" "id:1101,phase:2,deny,status:403,t:none,t:urlDecodeUni,t:lowercase"\n'
    f'SecRule ARGS "@rx {BIG}" "id:1102,phase:2,deny,status:403,t:none,t:sha1"\n'
)
N_SYNTHETIC_GROUPS = len(NATIVE_TRANSFORMS) + 3


@pytest.fixture(scope="module")
def crs_lite(native_lib):
    return native_engine(compile_rules_cached(load_ruleset_text(), CRS_CACHE_DIR), native_lib)


@pytest.fixture(scope="module")
def synthetic_crs():
    return compile_rules(SYNTHETIC_RULES)


@pytest.fixture(scope="module")
def synthetic(native_lib, synthetic_crs):
    return native_engine(synthetic_crs, native_lib)


def _rows(seed: int, width: int, witnesses: list[bytes]) -> list[bytes]:
    """What a window's rows look like, and what bends a transform: hex
    salts, percent- and entity-encodings, NULs, empty rows, rows exactly
    ``width`` long, and values that do match (plain and encoded)."""
    rng = random.Random(seed)
    hexd = "0123456789abcdef"
    noise = bytes(range(256))
    rows = [b"", b"\x00", b"%", b"%u", b"&#", b"/*", bytes(width)]
    for w in witnesses:
        rows += [
            w,
            b"q=" + w + b"&x=1",
            w.upper(),
            b"".join(b"%%%02x" % b for b in w),
            b"".join(b"%%u00%02X" % b for b in w),
            b"".join(b"&#%d;" % b for b in w),
            b"".join(b"&#x%x;" % b for b in w),
            base64.b64encode(w),
            w.hex().encode(),
            b"  " + w.replace(b" ", b" \t ") + b" \n",
            b"/*c*/" + w + b"--\n",
            b"/a/../" + w + b"/./",
            (w * (width // max(1, len(w)) + 1))[:width],
        ]
    for _ in range(12):
        rows.append(("q=" + "".join(rng.choices(hexd, k=300))).encode()[:width])
        rows.append(bytes(rng.choices(noise, k=rng.randrange(1, width + 1))))
        rows.append(
            "".join(
                rng.choice(["%3C", "%u0041", "&lt;", "&#x41;", "+", "%0", "\\x41", "a", "bc", "d", " "])
                for _ in range(rng.randrange(1, 60))
            ).encode()[:width]
        )
    rows.append(bytes(rng.choices(b"abcd", k=width)))  # exactly `width` long
    return [r[:width] for r in rows]


def _tier(eng: WafEngine, rows: list[bytes], width: int):
    """A tier tuple as the tensorizers lay it out — data, lengths and the
    host variant planes are all ``_confirm_prefilter`` reads — padded
    with zero-length rows; a positive on a pad row is walked too."""
    crs = eng.compiled
    u = len(rows) + 3
    data = np.zeros((u, width), dtype=np.uint8)
    lengths = np.zeros(u, dtype=np.int32)
    host = crs.host_pipelines()
    vdata = np.zeros((max(1, len(host)), u, width), dtype=np.uint8)
    vlengths = np.zeros((max(1, len(host)), u), dtype=np.int32)
    for i, r in enumerate(rows):
        data[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
        lengths[i] = len(r)
        for slot, (_pid, names) in enumerate(host):
            v = apply_pipeline(r, list(names))[:width]
            vdata[slot, i, : len(v)] = np.frombuffer(v, dtype=np.uint8)
            vlengths[slot, i] = len(v)
    return (data, lengths, None, None, None, None, vdata, vlengths)


def _confirm(eng: WafEngine, tier, ks, seed: int, native_on: bool = True):
    """Run ``_confirm_prefilter`` over a tier where every row is positive
    in the prefilter columns ``ks`` (random bits elsewhere, which must
    come back untouched). Returns (packed rows out, stats delta)."""
    g = int(eng.model.e_lg.shape[0])
    u = tier[0].shape[0]
    hits = np.random.default_rng(seed).integers(0, 2, size=(u, g), dtype=np.uint8)
    cols = np.asarray([c for c, _g in eng.model.prefilter_cols])
    hits[:, cols] = 0
    hits[:, cols[ks]] = 1
    packed = np.packbits(hits, axis=1)
    before = dict(eng.prefilter_stats)
    with pytest.MonkeyPatch.context() as mp:
        if not native_on:
            mp.setattr(eng._native_confirm, "handled", np.zeros(len(cols), dtype=bool))
        (out,) = eng._confirm_prefilter((packed,), (tier,), (True,), current_stages())
    delta = {k: v - before[k] for k, v in eng.prefilter_stats.items()}
    return np.asarray(out), delta


def _parity(eng: WafEngine, ks, seed: int, width: int = 96):
    cols = eng.model.prefilter_cols
    witnesses = [_witness(eng.compiled.groups[cols[k][1]].dfa) for k in ks]
    tier = _tier(eng, _rows(seed, width, witnesses), width)
    out_n, stats_n = _confirm(eng, tier, ks, seed)
    out_p, stats_p = _confirm(eng, tier, ks, seed, native_on=False)
    assert out_n.tobytes() == out_p.tobytes()
    n_pos = tier[0].shape[0] * len(ks)
    assert stats_n.pop("native_hits") == n_pos and stats_p.pop("native_hits") == 0
    assert stats_n == stats_p
    assert stats_n["hits"] == n_pos and stats_n["native_errors"] == 0
    assert stats_n["false_positives"] == stats_n["hits"] - stats_n["confirms"]
    return stats_n


CASES = (
    [("crs-lite", k) for k in range(N_CRS_LITE_GROUPS)]
    + [("crs-lite", "all")]
    + [("synthetic", k) for k in range(N_SYNTHETIC_GROUPS - 1)]
    + [("synthetic", "all")]
)


@pytest.mark.parametrize("which,k", CASES, ids=[f"{w}-{k}" for w, k in CASES])
def test_native_confirm_is_bit_identical_to_the_python_walk(which, k, request):
    """One case per prefiltered group (its column positive on every row),
    plus one per ruleset with every column positive — the (pipeline, row)
    memo shared across groups."""
    eng = request.getfixturevalue("crs_lite" if which == "crs-lite" else "synthetic")
    n = N_CRS_LITE_GROUPS if which == "crs-lite" else N_SYNTHETIC_GROUPS
    assert len(eng.model.prefilter_cols) == n
    handled = eng._native_confirm.handled
    if which == "crs-lite":
        assert handled.all()
        width = 512  # the cell's window width
    else:
        # Only the sha1 group (the last rule) stays on the Python walk.
        assert handled[:-1].all() and not handled[-1]
        width = 96
    ks = np.flatnonzero(handled).tolist() if k == "all" else [k]
    stats = _parity(eng, ks, seed=1000 + (0 if k == "all" else k), width=width)
    # The witnesses match: the walk's accepting side is exercised too, in
    # every group whose pipeline leaves some spelling of one intact.
    assert stats["confirms"] >= 1 or which == "synthetic"
    assert stats["false_positives"] >= 1


def test_most_synthetic_pipelines_confirm_something(synthetic):
    """The synthetic cases are not all-refuted: per pipeline, some
    encoding of the witness survives the transform and matches."""
    confirmed = 0
    for k in range(N_SYNTHETIC_GROUPS - 1):
        tier = _tier(synthetic, _rows(7, 96, [WITNESS]), 96)
        confirmed += _confirm(synthetic, tier, [k], 7)[1]["confirms"] >= 1
    assert confirmed >= 24, confirmed


# -- fallbacks ---------------------------------------------------------------


class _OlderLib:
    """The library as an older build exports it: no ``cko_confirm_*``."""

    def __init__(self, lib):
        self._real = lib

    def __getattr__(self, name):
        if name.startswith("cko_confirm_"):
            raise AttributeError(name)
        return getattr(self._real, name)


def _all_columns(eng, seed=5):
    ks = list(range(len(eng.model.prefilter_cols)))
    tier = _tier(eng, _rows(seed, 96, [WITNESS]), 96)
    return tier, ks


@pytest.mark.parametrize("library", ["absent", "older"])
def test_without_the_export_the_python_walk_answers(
    library, native_lib, synthetic, synthetic_crs
):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CKO_AUTOMATA", "1")
        if library == "absent":
            load_native(mp, None)
        else:
            load_native(mp, native_lib)
            older = _OlderLib(native.load_library())
            native._bind(older)
            assert older._cko_has_plan and not older._cko_has_confirm
            mp.setattr(native, "_lib", older)
        eng = WafEngine(synthetic_crs)
    assert not eng._native_confirm.handled.any()
    tier, ks = _all_columns(eng)
    out, stats = _confirm(eng, tier, ks, 5)
    ref, ref_stats = _confirm(synthetic, tier, ks, 5)
    assert out.tobytes() == ref.tobytes()
    assert stats["native_hits"] == 0 and stats["native_errors"] == 0
    assert ref_stats["native_hits"] > 0
    for key in ("rows", "hits", "confirms", "false_positives"):
        assert stats[key] == ref_stats[key]


def test_group_with_a_non_native_op_takes_the_python_walk(synthetic):
    sha1 = N_SYNTHETIC_GROUPS - 1
    tier, ks = _all_columns(synthetic)
    _out, only = _confirm(synthetic, tier, [sha1], 5)
    assert only["hits"] == tier[0].shape[0] and only["native_hits"] == 0
    # Mixed window: the sha1 column is walked in Python, the rest natively.
    out, mixed = _confirm(synthetic, tier, ks, 5)
    assert mixed["native_hits"] == mixed["hits"] - tier[0].shape[0]
    ref, ref_stats = _confirm(synthetic, tier, ks, 5, native_on=False)
    assert out.tobytes() == ref.tobytes()
    assert mixed["confirms"] == ref_stats["confirms"]


@pytest.mark.parametrize("failure", ["argument_rejected", "negative_rc"])
def test_failed_native_call_is_counted_logged_once_and_rewalked(
    failure, synthetic, caplog
):
    """A ctypes rejection or a negative rc must not demote the window in
    silence (the CKO-N004 class): counted per call, logged once, and the
    Python walk gives the same bits."""
    tier, ks = _all_columns(synthetic)
    if failure == "negative_rc":
        # A length beyond the row width: the library refuses it (rc -5);
        # NumPy's slice clamps it, so the Python walk reads the whole row.
        tier[1][0] = tier[0].shape[1] + 1
    ref, ref_stats = _confirm(synthetic, tier, ks, 5, native_on=False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(synthetic, "_native_confirm_failed", False)
        if failure == "argument_rejected":
            mp.setattr(synthetic._native_confirm, "_h", 1.5)  # no float is a c_void_p
        with caplog.at_level("ERROR", logger="cko"):
            out1, s1 = _confirm(synthetic, tier, ks, 5)
            out2, s2 = _confirm(synthetic, tier, ks, 5)
    for out, stats in ((out1, s1), (out2, s2)):
        assert out.tobytes() == ref.tobytes()
        assert stats["native_errors"] == 1 and stats["native_hits"] == 0
        assert stats["confirms"] == ref_stats["confirms"]
    logged = [r for r in caplog.records if "native prefilter confirm failed" in r.getMessage()]
    assert len(logged) == 1
    assert ("rejected" if failure == "argument_rejected" else "rc=-5") in logged[0].getMessage()
