"""Flat-slot fused multi-bank scan vs the plain bank scan's gather oracle.

The fused kernel (ops/dfa_flat.py) must agree exactly with
``scan_dfa_bank_gather`` on every bank it fuses — heterogeneous state
counts, multiple pipelines, group-split pieces, bf16/f32 table segments,
zero-length rows, end-anchored and always-match DFAs.
"""

import random

import numpy as np
import pytest

from coraza_kubernetes_operator_tpu.compiler import (
    compile_regex_dfa,
    literal_dfa,
    pm_dfa,
)
from coraza_kubernetes_operator_tpu.ops.dfa import (
    scan_dfa_bank_gather,
    scan_dfa_bank_take,
    stack_dfas,
)
from coraza_kubernetes_operator_tpu.ops.dfa_flat import (
    build_flat_bank,
    plan_flat_bins,
    scan_flat_bank,
    scan_flat_xla,
)

SMALL = [
    compile_regex_dfa("^/admin"),
    compile_regex_dfa(r"(?i:<script[^>]*>)"),
    literal_dfa(b"evilmonkey"),
    compile_regex_dfa("passwd$"),
    compile_regex_dfa("a*"),  # always-match
]
BIG = [
    compile_regex_dfa(
        r"(?i:(\b(select|union|insert|update|delete|drop)\b.*\b(from|into|where|table)\b))"
    ),
    pm_dfa([b"sleep", b"benchmark", b"waitfor", b"pg_sleep", b"dbms_lock"]),
    compile_regex_dfa(r"\bor\b\s*['\"]?\d+['\"]?\s*=\s*['\"]?\d+"),
]


def _batch(seed=7, n_extra=80, max_len=64):
    corpus = [
        b"",
        b"/admin/panel",
        b"select * from users",
        b"<script>alert(1)</script>",
        b"evilmonkey",
        b"/etc/passwd",
        b"passwd tail",
        b"or 1=1",
        b"benchmark(9)",
        b"a" * 63,
    ]
    rng = random.Random(seed)
    corpus += [
        bytes(
            rng.choice(b"abcdefor1=' <>script/untilfwm")
            for _ in range(rng.randrange(0, max_len))
        )
        for _ in range(n_extra)
    ]
    data = np.zeros((len(corpus), max_len), dtype=np.uint8)
    lengths = np.zeros(len(corpus), dtype=np.int32)
    for i, c in enumerate(corpus):
        c = c[:max_len]
        data[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
        lengths[i] = len(c)
    return data, lengths


def _oracle(dfas, data, lengths):
    bank = stack_dfas(dfas)
    return np.asarray(scan_dfa_bank_gather(bank, data, lengths))


def _flat_cols(flat, out, dfas_by_block):
    """Reassemble [B, G] per block from a fused bin's output columns."""
    per_block = {}
    col = 0
    for block_idx, g_lo, g_hi in flat.pieces:
        w = g_hi - g_lo
        per_block.setdefault(block_idx, {})[g_lo] = out[:, col : col + w]
        col += w
    return per_block


@pytest.mark.parametrize("path", ["xla", "interpret"])
def test_flat_matches_gather_oracle(path):
    # Two blocks on pipeline 0 (small + big states), one on pipeline 1 —
    # pipeline 1 sees DIFFERENT data so cross-pipeline wiring is real.
    data0, len0 = _batch(seed=7)
    data1, len1 = _batch(seed=99)
    banks = [(0, 0, SMALL), (1, 0, BIG), (2, 1, SMALL[:3])]
    bins, rejected = plan_flat_bins(banks, max_slots=100000)
    assert not rejected
    data_by_pipe = {0: (data0, len0), 1: (data1, len1)}

    got = {}
    for b in bins:
        flat = build_flat_bank(b)
        sub = {p: data_by_pipe[p] for p in set(flat.seg_pipes)}
        if path == "xla":
            out = np.asarray(scan_flat_xla(flat, sub))
        else:
            out = np.asarray(scan_flat_bank(flat, sub, interpret=True))
        for bi, cols in _flat_cols(flat, out, None).items():
            got.setdefault(bi, {}).update(cols)

    for bi, pid, dfas in banks:
        d, ln = data_by_pipe[pid]
        want = _oracle(dfas, d, ln)
        pieces = got[bi]
        out = np.concatenate([pieces[k] for k in sorted(pieces)], axis=1)
        np.testing.assert_array_equal(out, want, err_msg=f"block {bi}")


def test_flat_split_bank_equals_whole():
    """A bank split across bins by group range must yield the same
    columns as the unsplit oracle."""
    data, lengths = _batch(seed=3)
    dfas = SMALL + BIG
    max_slots = max(d.n_states for d in dfas) + 1  # forces splits
    bins, _rej = plan_flat_bins([(0, 0, dfas)], max_slots=max_slots)
    assert len(bins) >= 2
    cols = {}
    for b in bins:
        flat = build_flat_bank(b)
        out = np.asarray(scan_flat_xla(flat, {0: (data, lengths)}))
        col = 0
        for _bi, g_lo, g_hi in flat.pieces:
            cols[g_lo] = out[:, col : col + (g_hi - g_lo)]
            col += g_hi - g_lo
    got = np.concatenate([cols[k] for k in sorted(cols)], axis=1)
    want = _oracle(dfas, data, lengths)
    np.testing.assert_array_equal(got, want)


def test_flat_zero_length_rows():
    data = np.zeros((4, 32), dtype=np.uint8)
    lengths = np.zeros(4, dtype=np.int32)
    flat = build_flat_bank(plan_flat_bins([(0, 0, SMALL)])[0][0])
    out = np.asarray(scan_flat_xla(flat, {0: (data, lengths)}))
    want = _oracle(SMALL, data, lengths)
    np.testing.assert_array_equal(out, want)
    # always-match DFA (index 4) matches empty input; others don't.
    assert out[:, 4].all()
    assert not out[:, 0].any()


def test_vmem_planner_respects_budget():
    from coraza_kubernetes_operator_tpu.ops.dfa_flat import (
        _dfa_table_bytes,
        _FLAT_VMEM_BUDGET,
        flat_vmem_bytes,
    )

    from coraza_kubernetes_operator_tpu.ops.dfa_flat import _layout_stats

    dfas = (SMALL + BIG) * 12
    bins, _rej = plan_flat_bins([(i, i % 3, dfas) for i in range(4)], max_slots=4096)
    for b in bins:
        slots, groups, tbytes, pipes = _layout_stats(b)
        assert slots <= 4096
        assert (
            flat_vmem_bytes(slots, groups, tbytes, 2048, pipes)
            <= _FLAT_VMEM_BUDGET
        )


# --- crs-lite's dfa-hot and prefilter tiers in the flat bins (PR 31) -------
#
# build_model plans every dense-DFA block into flat bins. The bins must
# give, column for column, what the plain bank scans of ``ops/dfa.py``
# give over ``stack_dfas`` of the block's DFAs (``scan_dfa_bank_take``,
# ``scan_dfa_bank_gather``) and what the scalar ``DFA.search`` gives.

CRS_WIDTH = 64  # small: the interpreted Pallas kernel steps every byte


@pytest.fixture(scope="module")
def crs_lite():
    from pathlib import Path

    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules_cached
    from coraza_kubernetes_operator_tpu.engine import WafEngine
    from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text

    cache = str(Path(__file__).resolve().parent / ".crs_cache")
    with pytest.MonkeyPatch.context() as mp:
        for k in ("CKO_AUTOMATA", "CKO_NATIVE"):
            mp.delenv(k, raising=False)
        return WafEngine(compile_rules_cached(load_ruleset_text(), cache))


def _tier_blocks(eng, kind):
    """(block index, pipeline, the block's DFAs) for each dense block of
    ``kind``, the DFAs through ``group_order`` (device column -> original
    group), as the engine's confirm does: the exact DFA of a dfa-hot
    group, the approximation of a prefiltered one."""
    m = eng.model
    offs = np.concatenate([[0], np.cumsum(eng._block_group_counts)])
    if kind == "dfa-hot":
        dfa_of = lambda gid: eng.compiled.groups[gid].dfa  # noqa: E731
    else:
        dfa_of = lambda gid: eng.automata_plan.tiers[gid].approx  # noqa: E731
    out = []
    for blk, db in enumerate(m.dense_blocks, start=len(m.segs)):
        if db.kind == kind:
            gids = [m.group_order[c] for c in range(offs[blk], offs[blk + 1])]
            out.append((blk, db.pipeline, [dfa_of(g) for g in gids]))
    return out


def _crs_rows(dfas, width, seed):
    """Empty rows, rows of the full width, and for every DFA a row that
    is its shortest match alone (the match ends on the row's last byte)
    and the same at the end of a full-width row."""
    from conftest import dfa_witness

    rng = random.Random(seed)
    rows = [b"", b"", bytes(width), b"a" * width]
    for d in dfas:
        w = dfa_witness(d)[:width]
        rows += [w, (b"q=" + b"0123456789abcdef" * width + w)[-width:]]
    rows += [bytes(rng.choices(range(0x20, 0x7F), k=rng.randrange(0, width + 1)))
             for _ in range(24)]
    rows += [bytes(rng.choices(range(256), k=width)) for _ in range(8)]
    return rows


def _as_tensors(rows, width):
    data = np.zeros((len(rows), width), dtype=np.uint8)
    lengths = np.zeros(len(rows), dtype=np.int32)
    for i, r in enumerate(rows):
        data[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
        lengths[i] = len(r)
    return data, lengths


def _scan_bins(model, data_by_pipe, path):
    """Columns by block from every flat bin of the model."""
    cols = {}
    for flat in model.flat_banks:
        sub = {p: data_by_pipe[p] for p in set(flat.seg_pipes)}
        if path == "interpret":
            out = np.asarray(scan_flat_bank(flat, sub, interpret=True))
        else:
            out = np.asarray(scan_flat_xla(flat, sub))
        for blk, pieces in _flat_cols(flat, out, None).items():
            cols.setdefault(blk, {}).update(pieces)
    return {
        blk: np.concatenate([p[k] for k in sorted(p)], axis=1) for blk, p in cols.items()
    }


@pytest.fixture
def one_bf16_pass(monkeypatch):
    """What a TPU does to a dot at default precision, on the CPU: both
    operands rounded to bf16, one pass, f32 accumulation. The CPU and the
    Pallas interpreter multiply f32 exactly, so only this shows a number
    in a matmul that bf16 cannot hold (the chip showed PR 31 one: slot
    indexes above 256 in f32 dots)."""
    import jax.numpy as jnp

    from coraza_kubernetes_operator_tpu.ops import dfa_flat

    def dot(a, b):
        return jnp.dot(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )

    monkeypatch.setattr(dfa_flat, "_dot", dot)


@pytest.mark.parametrize("path", ["xla", "interpret", "xla-one-bf16-pass"])
def test_flat_two_digit_tables(path, request):
    """A DFA of more than 128 states packs values past 256: its table is
    two base-256 digit planes, beside one-plane segments in one bin."""
    if path == "xla-one-bf16-pass":
        request.getfixturevalue("one_bf16_pass")
    wide = compile_regex_dfa("(a|bc)*a(a|bc){7}d")
    assert 2 * wide.n_states > 256
    rng = random.Random(5)
    rows = [b"", b"xxaaaaaaaadxx", b"bcbcbcbcd", b"abcabcaabcaaad", b"a" * 64]
    rows += [bytes(rng.choices(b"abcd", k=rng.randrange(0, 65))) for _ in range(59)]
    data, lengths = _as_tensors(rows, 64)
    banks = [(0, 0, SMALL), (1, 0, [wide, BIG[0]]), (2, 1, [wide])]
    bins, rejected = plan_flat_bins(banks, max_slots=100000)
    assert not rejected and len(bins) == 1
    flat = build_flat_bank(bins[0])
    assert sorted(len(planes) for planes in flat.tables) == [1, 1, 2, 2]
    sub = {0: (data, lengths), 1: (data[::-1].copy(), lengths[::-1].copy())}
    if path == "interpret":
        out = np.asarray(scan_flat_bank(flat, sub, interpret=True))
    else:
        out = np.asarray(scan_flat_xla(flat, sub))
    got = _flat_cols(flat, out, None)
    for bi, pid, dfas in banks:
        d, ln = sub[pid]
        np.testing.assert_array_equal(got[bi][0], _oracle(dfas, d, ln), err_msg=f"block {bi}")
    assert got[1][0][1, 0] and not got[1][0][2, 0]  # the witness, and the bait


@pytest.mark.parametrize("path", ["xla", "interpret", "xla-one-bf16-pass"])
@pytest.mark.parametrize("tier,n_groups", [("dfa-hot", 19), ("prefilter", 11)])
def test_crs_lite_tier_in_flat_bins_matches_per_bank_oracles(
    crs_lite, tier, n_groups, path, request
):
    if path == "xla-one-bf16-pass":
        request.getfixturevalue("one_bf16_pass")
    # The bins hold slots to 767: past what one bf16 digit holds.
    assert max(fb.n_slots for fb in crs_lite.model.flat_banks) > 512
    blocks = _tier_blocks(crs_lite, tier)
    dfas = [d for _blk, _pid, ds in blocks for d in ds]
    assert len(dfas) == n_groups
    assert {blk for blk, *_ in blocks} <= set(crs_lite.model.flat_covered)
    rows = _crs_rows(dfas, CRS_WIDTH, seed=31)
    # Every pipeline sees the rows in another order, so that a bin wired
    # to the wrong pipeline's bytes cannot pass.
    pids = range(len(crs_lite.model.pipelines))
    rows_of = {p: rows[p:] + rows[:p] for p in pids}
    data_by_pipe = {p: _as_tensors(rows_of[p], CRS_WIDTH) for p in pids}
    got = _scan_bins(crs_lite.model, data_by_pipe, path)
    last_byte_hits = 0
    for blk, pid, ds in blocks:
        data, lengths = data_by_pipe[pid]
        bank = stack_dfas(ds)
        for oracle in (scan_dfa_bank_take, scan_dfa_bank_gather):
            np.testing.assert_array_equal(
                got[blk], np.asarray(oracle(bank, data, lengths)), err_msg=f"block {blk}"
            )
        scalar = np.array([[d.search(r) for d in ds] for r in rows_of[pid]])
        np.testing.assert_array_equal(got[blk], scalar, err_msg=f"block {blk}")
        for j, d in enumerate(ds):
            if d.always_match:
                continue
            for i, r in enumerate(rows_of[pid]):
                # A match that ends exactly on the row's last byte.
                last_byte_hits += bool(r) and got[blk][i, j] and not d.search(r[:-1])
    assert last_byte_hits >= n_groups


def test_engine_prefilter_columns_stay_approximate_in_flat_bins(crs_lite):
    """The served engine: ``prefilter_cols`` still names columns that
    hold the APPROXIMATION's answer (now out of a flat bin), the host
    confirm refutes a bait that matches the approximation only, and no
    dense-DFA block is left outside the bins."""
    from coraza_kubernetes_operator_tpu.compiler.transforms_host import apply_pipeline
    from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
    from coraza_kubernetes_operator_tpu.models.waf_model import apply_device_pipeline
    from coraza_kubernetes_operator_tpu.observability.stages import current

    summary = crs_lite.automata_summary()
    assert summary["per_bank_kernels"] == 0 and summary["flat_bins"] >= 2
    # the 15 groups whose only fault was a literal past MAX_SEG_LEN ride
    # the conv tier as chained pieces, not these bins
    assert summary["flat_groups"] == 34 and summary["flat_slots"] % 128 == 0
    assert summary["dfa_hot_blocks"] == 5 and summary["prefilter_blocks"] == 5
    assert summary["segment_split_groups"] == 15 and summary["segment_splits"] == 26

    # crs-lite has no device executable on the CPU at a window's shape:
    # run the bins over one tier's rows as match_tier does (each bin on
    # its pipelines' transformed bytes) and hand the packed hits to the
    # engine's own confirm.
    m, crs = crs_lite.model, crs_lite.compiled
    exact = {col: crs.groups[gid].dfa for col, gid in m.prefilter_cols}
    approx = {col: crs_lite.automata_plan.tiers[gid].approx for col, gid in m.prefilter_cols}
    rows = _crs_rows(list(exact.values()), CRS_WIDTH, seed=32)
    data, lengths = _as_tensors(rows, CRS_WIDTH)
    host = crs.host_pipelines()
    vdata = np.zeros((max(1, len(host)), len(rows), CRS_WIDTH), dtype=np.uint8)
    vlengths = np.zeros((max(1, len(host)), len(rows)), dtype=np.int32)
    for slot, (_pid, names) in enumerate(host):
        for i, r in enumerate(rows):
            v = apply_pipeline(r, list(names))[:CRS_WIDTH]
            vdata[slot, i, : len(v)] = np.frombuffer(v, dtype=np.uint8)
            vlengths[slot, i] = len(v)
    data_by_pipe = {}
    for pid, names in enumerate(m.pipelines):
        slot = m.host_variant_index[pid]
        if slot >= 0:
            data_by_pipe[pid] = (vdata[slot], vlengths[slot])
        else:
            data_by_pipe[pid] = apply_device_pipeline(data, lengths, names)
    by_block = _scan_bins(m, data_by_pipe, "xla")
    n_segs = len(m.segs)
    hits = np.zeros((len(rows), int(m.e_lg.shape[0])), dtype=np.uint8)
    col = sum(s.n_groups for s in m.segs)
    for blk in range(n_segs, n_segs + len(m.dense_blocks)):
        w = by_block[blk].shape[1]
        hits[:, col : col + w] = by_block[blk]
        col += w
    assert col == hits.shape[1]

    seen = {}
    for col_, gid in m.prefilter_cols:
        pid = crs.group_pipeline[gid]
        seen[col_] = [
            bytes(np.asarray(data_by_pipe[pid][0][i][: int(data_by_pipe[pid][1][i])]))
            for i in range(len(rows))
        ]
        want = np.array([approx[col_].search(v) for v in seen[col_]])
        np.testing.assert_array_equal(hits[:, col_].astype(bool), want, err_msg=f"col {col_}")
    tier = (data, lengths, None, None, None, None, vdata, vlengths)
    (out,) = crs_lite._confirm_prefilter(
        (np.packbits(hits, axis=1),), (tier,), (True,), current()
    )
    confirmed = np.unpackbits(np.asarray(out), axis=1)[:, : hits.shape[1]]
    baits = upheld = 0
    for col_ in exact:
        want = np.array([exact[col_].search(v) for v in seen[col_]])
        np.testing.assert_array_equal(
            confirmed[:, col_].astype(bool), want & hits[:, col_].astype(bool)
        )
        baits += int((hits[:, col_].astype(bool) & ~want).sum())
        upheld += int(want.sum())
    assert baits >= 1 and upheld >= len(exact)
    other = np.ones(hits.shape[1], dtype=bool)
    other[list(exact)] = False
    np.testing.assert_array_equal(confirmed[:, other], hits[:, other])

    # And through a device window, on a rule set the CPU can compile:
    # the bait is refuted and does not block, the real match does.
    from test_automata_routing import RULES as THREE_TIER_RULES

    eng = WafEngine(THREE_TIER_RULES)
    s3 = eng.automata_summary()
    assert s3["per_bank_kernels"] == 0 and s3["flat_bins"] >= 1
    assert s3["tiers"]["dfa-hot"] >= 1 and s3["tiers"]["prefiltered"] >= 1
    bait, real, hot = eng.evaluate(
        [
            HttpRequest(uri="/?q=bcbcbcbcd"),
            HttpRequest(uri="/?q=xxaaaaaaaadxx"),
            HttpRequest(uri="/?q=zzehzz"),
        ]
    )
    assert bait.allowed and real.rule_id == 101 and hot.rule_id == 100
    assert eng.prefilter_stats["false_positives"] >= 1
    assert eng.prefilter_stats["confirms"] >= 1
