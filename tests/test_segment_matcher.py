"""Differential tests: conv-segment matcher vs Python ``re``.

The segment tier must be *exact* (compiler/segments.py's contract):
every pattern the decomposer accepts is replayed against Python ``re``
on randomized word soup plus targeted edge inputs, byte for byte.
"""

import functools
import random
import re

import numpy as np
import pytest

from coraza_kubernetes_operator_tpu.compiler.re_parser import parse_regex
from coraza_kubernetes_operator_tpu.compiler.segments import plan_segments
from coraza_kubernetes_operator_tpu.ops.segment import (
    build_segment_block,
    match_segment_block,
)

PATTERNS = [
    (r"evilmonkey", False),
    (r"union\s+select", True),
    (r"\bunion\s+(all\s+)?select\b", True),
    (r"select\b.+\bfrom", True),
    (r"<script[^>]*>", True),
    (r"on(error|load|click)\s*=", True),
    (r"\battack42x7\b\s*=\s*\d+", True),
    (r"(or|and)\b\s+\d+\s*=\s*\d+", True),
    (r"sleep\s*\(\s*\d+\s*\)", True),
    (r"\.\./", False),
    (r"etc/passwd", True),
    (r"javascript:", True),
    (r"a{2,4}b", False),
    (r"^/admin", False),
    (r"\.php$", False),
    (r"x\d{3}y", False),
    (r"ab?c", False),
    (r"information_schema", True),
    (r"\$\(.*\)", False),
    (r";\s*(cat|ls|id|whoami)\b", True),
    # CRS-grade shapes: wide bounded class gaps (windowed-min path) and
    # alternation products
    (r"select\b[^;]{0,40}\bfrom", True),
    (r"<(img|svg|iframe)[^>]{0,60}(onerror|onload)\s*=", True),
    (r"\b(select|update|delete)\b.{2,50}\b(from|where)\b", True),
]

WORDS = [
    "<img ", "src=x ", "onerror", "=y", "from", "where", "update ", ";;",
    "a"*45, "<svg "," onload", "delete ",
    "union", "select", "all", "from", "attack42x7", "or", "and", "sleep",
    "<script", ">", "=", "1", "23", " ", "  ", "\t", "evilmonkey", "../",
    "etc/passwd", "javascript:", "aab", "aaaab", "x123y", "x12y", "abc",
    "ac", "/admin", "q.php", "zz", "UNION", "SELECT", "On", "onload",
    "onerror ", "$(id)", ";cat ", "; ls", "information_schema",
]

EDGES = [
    b"", b"union select", b"unionselect", b"union  all select",
    b"xunion selectx", b"select * from t", b"selectx from", b"<script>",
    b"<script src=x>", b"< script>", b"attack42x7=9", b"attack42x7 = 12",
    b"attack42x7x=1", b"or 1=1", b"nor 1=1", b"sleep (5)", b"sleep(x)",
    b"a/admin", b"/admin", b"x.php", b"x.phpz", b"x123y", b"x1234y",
    b"onclick =x", b"ONLOAD=", b"aab", b"ab", b"ac", b"abc",
    b"\x00union select\x00", b"$()", b"$(cat /etc/x)", b";whoami",
]


@pytest.fixture(scope="module")
def corpus():
    rng = random.Random(0)
    corpus = []
    for _ in range(300):
        n = rng.randrange(0, 8)
        corpus.append("".join(rng.choice(WORDS) for _ in range(n)).encode())
    corpus += EDGES
    return corpus


def test_every_pattern_decomposes():
    for pat, ci in PATTERNS:
        ast = parse_regex(pat, case_insensitive=ci)
        assert plan_segments(ast) is not None, pat


def test_matcher_matches_python_re(corpus):
    plans = []
    for pat, ci in PATTERNS:
        plans.append(plan_segments(parse_regex(pat, case_insensitive=ci)))
    block = build_segment_block(plans)

    max_len = max(32, max(len(c) for c in corpus))
    data = np.zeros((len(corpus), max_len), dtype=np.uint8)
    lengths = np.zeros(len(corpus), dtype=np.int32)
    for i, c in enumerate(corpus):
        data[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
        lengths[i] = len(c)

    hits = np.asarray(match_segment_block(block.kernel, block.spec, data, lengths))
    for gi, (pat, ci) in enumerate(PATTERNS):
        oracle = re.compile(pat.encode(), re.IGNORECASE if ci else 0)
        for i, c in enumerate(corpus):
            want = oracle.search(c) is not None
            assert bool(hits[i, gi]) == want, (pat, c)


def test_fallback_patterns_stay_on_dfa_tier():
    # Constructs the decomposer must NOT accept (unbounded composite
    # repetition, wide bounded class gaps, lookarounds are parse errors).
    for pat in [r"(ab)+c", r"a[bc]{0,40}d", r"(xy){5}z" * 6]:
        plan = plan_segments(parse_regex(pat))
        if plan is not None:
            # If accepted it must still be exact — spot check quickly.
            block = build_segment_block([plan])
            oracle = re.compile(pat.encode())
            samples = [b"abc", b"ababc", b"ad", b"a" + b"b" * 39 + b"d", b""]
            max_len = 64
            data = np.zeros((len(samples), max_len), dtype=np.uint8)
            lengths = np.zeros(len(samples), dtype=np.int32)
            for i, s in enumerate(samples):
                data[i, : len(s)] = np.frombuffer(s, dtype=np.uint8)
                lengths[i] = len(s)
            hits = np.asarray(
                match_segment_block(block.kernel, block.spec, data, lengths)
            )
            for i, s in enumerate(samples):
                assert bool(hits[i, 0]) == (oracle.search(s) is not None), (pat, s)


def test_group_routing_in_model():
    """build_model routes decomposable groups to the segment tier and the
    rest to DFA banks; verdicts agree either way (engine-level parity is
    covered by tests/test_engine_e2e.py on the same corpus)."""
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
    from coraza_kubernetes_operator_tpu.models.waf_model import build_model

    rules = "\n".join(
        [
            "SecRuleEngine On",
            'SecDefaultAction "phase:2,log,deny,status:403"',
            'SecRule ARGS "@rx \\bunion\\s+select\\b" "id:1,phase:2,deny,status:403"',
            'SecRule ARGS "@rx (ab)+c" "id:2,phase:2,deny,status:403"',
        ]
    )
    model = build_model(compile_rules(rules))
    assert sum(s.n_groups for s in model.segs) >= 1
    assert sum(b.groups for b in model.dense_blocks) >= 1


def test_finals_tier_matches_python_re():
    """The conv + AND-any finals tier agrees with Python ``re`` on one
    block of five patterns whose last segment decides the match."""
    import re

    import jax.numpy as jnp
    import numpy as np

    from coraza_kubernetes_operator_tpu.compiler.re_parser import parse_regex
    from coraza_kubernetes_operator_tpu.compiler.segments import plan_segments
    from coraza_kubernetes_operator_tpu.ops import segment as S

    pats = [
        r"\bunion\s+select\b",
        r"attack\d+\s*=\s*\d+",
        r"drop\s+table",
        r"<script[^>]*>",
        r"eval\s*\(",
    ]
    plans = [plan_segments(parse_regex(p)) for p in pats]
    assert all(p is not None for p in plans)
    blk = S.build_segment_block(plans)

    texts = [
        b"union  select a from b",
        b"x attack123 = 99 y",
        b"DROP TABLE users",  # case-sensitive pattern: no match
        b"<script src=a>",
        b"eval (payload)",
        b"nothing to see",
        b"union of selections",
        b"attack7=3",
    ]
    T = 64
    L = 32
    data = np.zeros((T, L), dtype=np.uint8)
    lengths = np.zeros(T, dtype=np.int32)
    for i, txt in enumerate(texts):
        data[i, : len(txt)] = list(txt)
        lengths[i] = len(txt)

    got = np.asarray(
        S.match_segment_block(blk.kernel, blk.spec, jnp.asarray(data), jnp.asarray(lengths))
    )
    for i, txt in enumerate(texts):
        for gi, p in enumerate(pats):
            want = re.search(p.encode(), txt) is not None
            assert bool(got[i, gi]) == want, (p, txt)
    assert not got[len(texts) :].any()  # padding rows match nothing


GAPCLS_PATTERNS = [(r"<script[^>]*>", True), (r"select\b.+\bfrom", True)]


@pytest.mark.parametrize("max_len", [582, 2048])
def test_gapcls_deep_in_a_wide_row_matches_python_re(max_len):
    """A class gap whose match lies deep in a wide row (q = max_len + 2:
    three blocks of the prefix count at 582, nine at 2048, the match in
    the last) stays byte-exact vs Python re."""
    pats = GAPCLS_PATTERNS
    plans = [plan_segments(parse_regex(p, case_insensitive=ci)) for p, ci in pats]
    block = build_segment_block(plans)

    deep = max_len - 42
    rng = random.Random(7)
    rows = [
        b"x" * max_len,
        # positives with the match DEEP in the buffer (past the first
        # blocks of 256 positions) — must fit inside max_len
        (b"z" * deep) + b"<script src=a>" + b"y" * 20,
        b"select " + b"a" * (deep - 10) + b" from t",
        b"<script" + b">" * 1,  # short content, long bucket
        bytes(rng.randrange(32, 127) for _ in range(max_len)),
        # the class gap spans block boundaries; one byte outside the
        # class in the middle of it must not break the match: `[^>]*`
        # restarts at the later `<script`
        b"<script " + b"a" * 300 + b"<script " + b"b" * (deep - 320) + b">",
        b"<script " + b"a" * (max_len - 8),  # never closed
    ]
    assert all(len(c) <= max_len for c in rows)
    data = np.zeros((len(rows), max_len), dtype=np.uint8)
    lengths = np.zeros(len(rows), dtype=np.int32)
    for i, c in enumerate(rows):
        data[i, : len(c)] = np.frombuffer(c[:max_len], dtype=np.uint8)
        lengths[i] = min(len(c), max_len)

    hits = np.asarray(match_segment_block(block.kernel, block.spec, data, lengths))
    for gi, (pat, ci) in enumerate(pats):
        oracle = re.compile(pat.encode(), re.IGNORECASE if ci else 0)
        for i, c in enumerate(rows):
            want = oracle.search(c[:max_len]) is not None
            assert bool(hits[i, gi]) == want, (pat, i)
    assert hits[1, 0] and hits[2, 1] and hits[5, 0] and not hits[6, 0]


PREFIX_ROWS = {
    "zeros": lambda rng, q: np.zeros((3, q), dtype=bool),
    "ones": lambda rng, q: np.ones((3, q), dtype=bool),  # the count reaches q - 1
    "random": lambda rng, q: rng.random((5, q)) < np.array([[0.5], [0.03], [0.97], [0.5], [0.999]]),
}


@pytest.mark.parametrize("rows", sorted(PREFIX_ROWS))
@pytest.mark.parametrize("q", [1, 66, 255, 256, 257, 514, 2050, 8194, 131_074])
def test_excl_prefix_count_is_cumsum_minus_self(q, rows):
    """One block (q ≤ 256), two, three, nine, thirty-three, and 513 blocks
    whose totals are themselves counted in three blocks: bit for bit the
    exclusive prefix sum, with no table that grows with q."""
    from coraza_kubernetes_operator_tpu.ops.segment import _excl_prefix_count

    x = PREFIX_ROWS[rows](np.random.default_rng(q), q)
    got = np.asarray(_excl_prefix_count(x))
    assert got.dtype == np.int32 and got.shape == x.shape
    np.testing.assert_array_equal(got, np.cumsum(x, axis=1) - x)


def _jaxprs(closed):
    """A closed jaxpr and every one beneath it, each with its constants."""
    from jax.extend import core

    yield closed.jaxpr, closed.consts
    for eqn in closed.jaxpr.eqns:
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, core.ClosedJaxpr):
                    yield from _jaxprs(sub)
                elif isinstance(sub, core.Jaxpr):
                    yield from _jaxprs(core.ClosedJaxpr(sub, ()))


@pytest.mark.parametrize("width", [2048, 131_072])
def test_no_scan_primitive_and_no_table_that_grows_with_the_width(width):
    """What the old width threshold stood for, held on the program: at a
    body tier's width the class-gap block traces to no cumulative
    primitive (they lower to reduce-window trees on a TPU) and to no
    constant of more than B² elements (a [Q, Q] table is a
    request-triggerable multi-GB allocation); and every matmul of the
    prefix counts takes bf16 operands, so that what one pass of the MXU
    computes is what the CPU computed in the tests above."""
    import jax
    import jax.numpy as jnp

    from coraza_kubernetes_operator_tpu.ops import segment as seg_mod

    plans = [plan_segments(parse_regex(p, case_insensitive=ci)) for p, ci in GAPCLS_PATTERNS]
    block = build_segment_block(plans)
    closed = jax.make_jaxpr(
        lambda k, d, ln: match_segment_block(k, block.spec, d, ln)
    )(block.kernel, jax.ShapeDtypeStruct((2, width), jnp.uint8), jax.ShapeDtypeStruct((2,), jnp.int32))
    primitives, largest = set(), 0
    for jaxpr, consts in _jaxprs(closed):
        largest = max([largest] + [int(np.size(c)) for c in consts])
        for eqn in jaxpr.eqns:
            primitives.add(eqn.primitive.name)
            largest = max([largest] + [int(np.size(v.val)) for v in eqn.invars if hasattr(v, "val")])
    assert "dot_general" in primitives and "conv_general_dilated" in primitives
    assert not {p for p in primitives if p.startswith(("cum", "reduce_window"))}, primitives
    assert largest <= seg_mod._PREFIX_BLOCK ** 2, largest

    counted = jax.make_jaxpr(seg_mod._excl_prefix_count)(jax.ShapeDtypeStruct((2, width + 2), jnp.bool_))
    dots = [e for jaxpr, _ in _jaxprs(counted) for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == (2 if width == 2048 else 3)  # a level a matmul: 9 blocks; 513, then 3
    for eqn in dots:
        assert all(v.aval.dtype == jnp.bfloat16 for v in eqn.invars)
        assert eqn.outvars[0].aval.dtype == jnp.float32


# -- the unbounded forward class gap as reachability matmuls (ISSUE 44) ---------------------

SPACE = ((9, 13), (32, 32))  # \\s: a NUL is outside it
NOT_AMP = ((0, 37), (39, 255))  # [^&]: a NUL is inside it, so a run enters the zero tail


def _reach_rows(kind: str, q: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """(bytes [T, q] as ``dpad[:, :q]`` holds them: NUL, the row, NULs;
    lengths [T]) for one kind of row."""
    body = q - 2
    rows = []
    if kind == "one_boundary":  # a class run across position 128 (where q has one)
        lo, hi = max(1, min(100, body - 8)), max(2, min(140, body))
        rows = [b"&" * (lo - 1) + b" " * (hi - lo) + b"&x", b"x" * (lo - 1) + b"\t" * (hi - lo)]
    elif kind == "several_boundaries":  # one run over nearly the whole row, and one broken in the middle
        rows = [b"&&" + b" " * (body - 5) + b"&a", b" " * (body // 2) + b"&" + b" " * (body - body // 2 - 1)]
    elif kind == "to_the_end_and_the_tail":  # runs that end with the row; NULs follow
        rows = [b"&&&" + b"a" * (body - 3), b"&" + b" " * (body - 1), b"a" * (body // 3)]
    elif kind == "empty_rows":
        rows = [b"", b"", b" "]
    else:
        for density in (0.3, 0.7, 0.9, 0.97):
            n = int(rng.integers(body // 2, body + 1))
            rows.append(bytes(np.where(rng.random(n) < density, 32, 38).astype(np.uint8)))
    rows = [r[:body] for r in rows]
    data = np.zeros((len(rows), q), dtype=np.int32)
    for i, r in enumerate(rows):
        data[i, 1 : 1 + len(r)] = np.frombuffer(r, dtype=np.uint8)
    return data, np.array([len(r) for r in rows], dtype=np.int32)


@pytest.mark.parametrize("ivs", [SPACE, NOT_AMP], ids=["space", "not_amp"])
@pytest.mark.parametrize("lo", [0, 1, 3])
@pytest.mark.parametrize("q", [34, 258, 514, 2050])
@pytest.mark.parametrize(
    "kind", ["one_boundary", "several_boundaries", "to_the_end_and_the_tail", "empty_rows", "random"])
def test_reach_gap_is_the_latch_bit_for_bit(kind, q, lo, ivs):
    """``_reach_gap`` over ``_reach_tables`` against ``_latch_min(...) ==
    nce`` under the same ``lo`` preamble as ``gap_cls``: class runs that
    cross one and several blocks of ``_REACH_BLOCK`` positions, that end
    with the row and travel the zero tail, rows of length 0, Q no multiple
    of the block; sparse, dense and planted ``x``."""
    import jax.numpy as jnp

    from coraza_kubernetes_operator_tpu.ops import segment as seg_mod

    rng = np.random.default_rng(q * 7 + lo)
    dpad, lengths = _reach_rows(kind, q, rng)
    t = dpad.shape[0]
    nce = seg_mod._excl_prefix_count(~seg_mod._in_class(ivs, jnp.asarray(dpad)))
    nce3, big = nce[..., None], jnp.int32(1 << 20)
    ns = 5
    x = rng.random((t, q, ns)) < np.array([0.0, 0.004, 0.05, 0.6, 0.0])
    for i in range(t):  # planted: one hit just past the row's last byte, as a suffix's base has it
        x[i, 1 + lengths[i], 4] = True
    x = jnp.asarray(x)
    if lo:
        clean = (jnp.pad(nce3, ((0, 0), (0, lo), (0, 0)), constant_values=big)[:, lo:] - nce3) == 0
        x = seg_mod._lshift3(x, lo) & clean
    want = seg_mod._latch_min(jnp.where(x, nce3, big), big, forward=True) == nce3
    got = seg_mod._reach_gap(x, seg_mod._reach_tables(nce, big))
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[:, :, 0].any()
    if kind != "empty_rows" and lo == 0:
        assert np.asarray(got)[:, :, 4].sum() > t  # the planted hit is seen from before it


def _signature_block(n_rules: int, lo: int):
    """``n_rules`` parameter signatures ``tok\\s*\\(\\s*['"]?tok`` (two
    unbounded class gaps, every rule a suffix of its own under ONE
    structure) and ``n_rules`` spaced pairs ``tokx\\s{lo,}tok`` under
    another. The planner peels a repetition's minimum into the segment
    before it, so the pairs are planned as ``\\s*`` and their gap's ``lo``
    is written into the spec here: (patterns as Python re reads them, block)."""
    import dataclasses

    names = [(f"zq{i:03d}k", f"wv{i:03d}j") for i in range(n_rules)]
    planned = [p for a, b in names for p in (rf"{a}\s*\(\s*['\"]?{b}", rf"{a}x\s*{b}")]
    plans = [plan_segments(parse_regex(p, case_insensitive=False)) for p in planned]
    assert all(p is not None for p in plans)
    block = build_segment_block(plans)
    branches = tuple(
        (gid, tuple(("gapcls", el[1], lo, el[3]) if gid % 2 and el[0] == "gapcls" else el for el in prog),
         a_start, a_end)
        for gid, prog, a_start, a_end in block.spec.branches)
    pats = [p for a, b in names for p in (rf"{a}\s*\(\s*['\"]?{b}", rf"{a}x\s{{{lo},}}{b}")]
    return pats, dataclasses.replace(block, spec=dataclasses.replace(block.spec, branches=branches))


@pytest.mark.parametrize("lo", [1, 3])
@pytest.mark.parametrize("n_rules", [15, 16, 17])
def test_a_structure_takes_the_matmul_from_the_threshold_on(n_rules, lo, monkeypatch):
    """Structures of 15, 16 and 17 columns around a threshold patched to
    the elements of a 16-column block over these rows: ``reach_gap_count``
    follows the block's size alone, the trace holds the latch's ``min``
    passes below it and the blocked matmuls from it on, and both forms give
    Python re's answers on rows whose gaps cross a block, with a gap's
    ``lo`` at 0, 1 and 3."""
    import jax

    from coraza_kubernetes_operator_tpu.ops import segment as seg_mod

    pats, block = _signature_block(n_rules, lo)
    wide = 3 if n_rules >= 16 else 0  # two gaps in the signatures' structure, one in the pairs'
    max_len = 300
    rng = random.Random(n_rules)
    rows = [b"zq003k" + b" " * 140 + b"(" + b"\t" * 120 + b"'wv003j",  # both gaps cross position 128
            b"zq003k" + b" " * 140 + b"(" + b" " * 60 + b"x" + b" " * 60 + b"'wv003j",  # broken
            b"zq004k('wv004j", b"zq004k(''wv004j", b"zq005kx wv005j", b"zq004kx   wv004j",
            b"zq004kx  wv004j", b"zq004kxwv004j", b"a" * 120 + b"zq001kx" + b" " * 150 + b"wv001j",
            b"zq002k (" + b" " * 280, b"zq002kx" + b" " * 293, b""]
    rows += [bytes(rng.choice(b"zqwvkj0123( '\"x") for _ in range(rng.randrange(max_len))) for _ in range(6)]
    data = np.zeros((len(rows), max_len), dtype=np.uint8)
    for i, r in enumerate(rows):
        data[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
    lengths = np.array([len(r) for r in rows], dtype=np.int32)
    sixteen = len(rows) * (max_len + 2) * 16  # a 16-column structure's [T, Q, ns] block
    monkeypatch.setattr(seg_mod, "_REACH_MIN_ELEMS", sixteen)
    assert seg_mod.reach_gap_count(block.spec, len(rows), max_len + 2) == wide
    assert seg_mod.reach_gap_count(block.spec, len(rows) - 1, max_len + 2) == (3 if n_rules > 16 else 0)

    def traced(threshold):
        monkeypatch.setattr(seg_mod, "_REACH_MIN_ELEMS", threshold)
        fn = jax.jit(lambda k, d, ln: match_segment_block.__wrapped__(k, block.spec, d, ln))
        closed = jax.make_jaxpr(fn)(block.kernel, data, lengths)
        eqns = [e for jaxpr, _ in _jaxprs(closed) for e in jaxpr.eqns]
        blocked = sum(1 for e in eqns
                      if e.primitive.name == "dot_general" and all(v.aval.ndim == 4 for v in e.invars))
        mins = sum(1 for e in eqns if e.primitive.name == "min")
        return np.asarray(fn(block.kernel, data, lengths)), mins, blocked

    latch, latch_mins, latch_dots = traced(2**40)
    reach, reach_mins, reach_dots = traced(sixteen)
    assert latch_dots == 0 and latch_mins > 0
    assert reach_dots == wide and reach_mins == (0 if wide else latch_mins)
    np.testing.assert_array_equal(reach, latch)
    for gi, pat in enumerate(pats):
        oracle = re.compile(pat.encode())
        for i, r in enumerate(rows):
            assert bool(reach[i, gi]) == (oracle.search(r) is not None), (pat, i)
    assert reach[0, 6] and not reach[1, 6] and reach[8, 3] and reach[5, 9] and not reach[7, 9]


# -- the conv's taps packed into the MXU's depth (ISSUE 47) ---------------------------------

# One more embed channel each: a class that is no product of nibble sets.
_CLASS_FILL = [rf"q[{chr(a)}-{chr(b)}]" for a in range(ord("g"), ord("p")) for b in range(ord("p"), ord("x"))]
_W26 = [r"\babcdefghijklmnopqrstuvwx\b", r"union\s+select", r"^/admin", r"\.php$"]
# id, base patterns, a match of the first, C channels, (k, taps), width (Q = width + 2), one row alone
PACK_CASES = [
    ("w26_c36_k3_w_no_multiple", _W26, b"abcdefghijklmnopqrstuvwx", 36, (3, 9), 97, False),
    ("w26_c36_k3_q_no_multiple", _W26, b"abcdefghijklmnopqrstuvwx", 36, (3, 9), 98, False),
    ("w24_c26_k4", [r"abcdefghijklmnopqrstuvwx", r"^pq", r"rs$"], b"abcdefghijklmnopqrstuvwx", 26, (4, 6), 100, False),
    ("w11_c16_k8_two_taps", [r"abcdefghijk", r"cab$", r"^bad"], b"abcdefghijk", 16, (8, 2), 64, False),
    ("c65_k1_the_plain_program", [r"hello\s+world", r"^he", r"ld$"], b"hello \tworld", 65, (1, 6), 64, False),
    ("one_row", _W26, b"abcdefghijklmnopqrstuvwx", 36, (3, 9), 40, True),
    ("w8_c14_k_no_more_than_w", [r"evilmonk", r"^bad"], b"evilmonk", 14, (8, 1), 33, False),
]


def _block_of_channels(base: list[str], channels: int):
    """``base`` and as many one-class fillers as bring the block's embed to
    ``channels`` planes: (patterns, block)."""
    pats, fill = list(base), iter(_CLASS_FILL)
    while True:
        block = build_segment_block([plan_segments(parse_regex(p)) for p in pats])
        if len(block.spec.channels) >= channels:
            return pats, block
        pats.append(next(fill))


@pytest.mark.parametrize("case", PACK_CASES, ids=[c[0] for c in PACK_CASES])
def test_packed_taps_give_the_plain_convs_hits_bit_for_bit(case, monkeypatch):
    """``128 // C`` taps of a block's kernel ride one contraction
    (``conv_tap_packing``): the group hits are the plain conv's (one tap a
    contraction: the packing patched to 1) bit for bit and Python re's, on
    rows whose matches start on the first byte and end on the last of a row
    as wide as the tier, with W and Q multiples of k and not; the traced
    conv is ``ceil(W / k)`` taps of ``k·C`` channels dilated by k (one tap
    of ``W·C`` where the kernel has fewer taps than fit); and a block of
    more than 64 channels traces the plain program."""
    import jax

    from coraza_kubernetes_operator_tpu.ops import segment as seg_mod

    _id, base, first, channels, (k, taps), width, one_row = case
    pats, block = _block_of_channels(base, channels)
    spec = block.spec
    assert len(spec.channels) == channels and seg_mod.conv_tap_packing(spec) == (k, taps)
    assert taps == -(-spec.w // k) and k == max(1, min(spec.w, 128 // channels))
    assert seg_mod.conv_passes(spec) == taps and seg_mod.conv_fill(spec) == k * channels / 128

    rng = random.Random(width)
    words = [w.encode() for w in ("abcdefghijklmnopqrstuvwx", "abcdefghijk", "union", " select", "/admin",
                                  "a.php", "pq", "rs", "cab", "bad", "hello ", "\tworld", "he", "ld", "qk", "evilmonk",
                                  "qp", "qs", " ", "-", "x")]
    noise = bytes(rng.choice(b" -.;=") for _ in range(width))
    rows = [first + noise[: width - len(first) - 3],  # starts on the first byte
            noise[: width - len(first)] + first,  # ends on the last byte of a row as wide as the tier
            noise[: width - len(first) - 1] + first + b" ",  # ... and one byte short of it
            b"/admin" + noise[:20] + b"a.php", b"x/admin a.phpx", b"pq" + noise[:9] + b"rs", b"bad cab", b"he ld",
            first[:-1], first[1:], b""]
    rows += [b"".join(rng.choice(words) for _ in range(rng.randrange(12)))[:width] for _ in range(24)]
    if one_row:
        rows = rows[1:2]
    data = np.zeros((len(rows), width), dtype=np.uint8)
    for i, r in enumerate(rows):
        data[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
    lengths = np.array([len(r) for r in rows], dtype=np.int32)

    def traced():
        fn = jax.jit(lambda kern, d, ln: match_segment_block.__wrapped__(kern, spec, d, ln))
        closed = jax.make_jaxpr(fn)(block.kernel, data, lengths)
        (conv,) = [e for jaxpr, _ in _jaxprs(closed) for e in jaxpr.eqns
                   if e.primitive.name == "conv_general_dilated"]
        return np.asarray(fn(block.kernel, data, lengths)), conv, str(closed)

    packed, conv, packed_program = traced()
    monkeypatch.setattr(seg_mod, "conv_tap_packing", lambda s: (1, s.w))
    plain, plain_conv, plain_program = traced()

    n2 = seg_mod.conv_n2_cols(spec)
    q = width + 2
    assert plain_conv.params["rhs_dilation"] == (1,)
    assert [tuple(v.aval.shape) for v in plain_conv.invars] == [(len(rows), q + spec.w - 1, channels),
                                                                (spec.w, channels, n2)]
    assert conv.params["rhs_dilation"] == (k,)
    assert [tuple(v.aval.shape) for v in conv.invars] == [(len(rows), q + k * (taps - 1), k * channels),
                                                          (taps, k * channels, n2)]
    assert tuple(conv.outvars[0].aval.shape) == tuple(plain_conv.outvars[0].aval.shape) == (len(rows), q, n2)
    assert (packed_program == plain_program) == (k == 1)

    np.testing.assert_array_equal(packed, plain)
    for gi, pat in enumerate(pats):
        oracle = re.compile(pat.encode())
        for i, r in enumerate(rows):
            assert bool(packed[i, gi]) == (oracle.search(r) is not None), (pat, r)
    assert packed[0, 0] and (one_row or (packed[1, 0] and packed[2, 0] and not packed[8:11, 0].any()))


def test_conv_n2_cols_matches_trace_allocation():
    """conv_n2_cols must equal len(col_order) as match_segment_block
    builds it — the HBM budget in segment_tier_hits depends on it."""
    from coraza_kubernetes_operator_tpu.ops.segment import conv_n2_cols

    plans = []
    for pat, ci in PATTERNS:
        plans.append(plan_segments(parse_regex(pat, case_insensitive=ci)))
    block = build_segment_block(plans)
    spec = block.spec

    # Reproduce the trace-time classification/allocation column count.
    n_cols = 0
    suffixes = set()
    for _, prog, _, a_end in spec.branches:
        if len(prog) >= 2 and prog[0][0] == "seg":
            n_cols += 1
            suffixes.add((prog[1:], a_end))
        else:
            n_cols += sum(1 for el in prog if el[0] == "seg")
    for ops, _ in suffixes:
        n_cols += sum(1 for el in ops if el[0] == "seg")
    assert conv_n2_cols(spec) == max(1, n_cols)
    # Duplication means N2 >= the deduped kernel column count is NOT
    # guaranteed per-spec, but for this corpus (shared segments across
    # branches) the duplicated count must be >= distinct segments used.
    assert conv_n2_cols(spec) >= 1


def test_shared_classes_distinct_geometry_no_collision():
    """Regression (found by the host-fallback parity gate on CRS 942120):
    two plans whose segments share the same byte-class sequence but with
    different lead/trail geometry (a one-byte LEAD context in one plan,
    the same class as a TRAILING lookahead in another — the ``\\b``
    encodings produce exactly this) must intern to DISTINCT conv
    columns. Keying the intern on classes alone made the later plan
    inherit the first one's (n_lead, n_real) shifts — an order-dependent
    false negative on CRS rules."""
    from coraza_kubernetes_operator_tpu.compiler.re_parser import ALL_BYTES
    from coraza_kubernetes_operator_tpu.compiler.segments import (
        Branch,
        Gap,
        Seg,
        SegmentPlan,
    )

    ck = 1 << ord("k")  # the shared byte class
    cx = 1 << ord("x")
    gap = Gap(mask=ALL_BYTES, lo=0, hi=None)
    # Plan A ≈ /x.*(?=k)/ : 'x', any gap, then (k) as trailing lookahead.
    plan_a = SegmentPlan(
        branches=(
            Branch(
                elements=(
                    Seg(classes=(cx,)),
                    gap,
                    Seg(classes=(ck,), n_lead=0, n_trail=1),
                ),
                anchored_start=False,
                anchored_end=False,
            ),
        ),
        always=False,
    )
    # Plan B ≈ /(?<=k)x/ : (k) as a one-byte lead context IMMEDIATELY
    # followed by 'x' — adjacency makes the lead shift load-bearing (an
    # unbounded gap would absorb an off-by-one).
    plan_b = SegmentPlan(
        branches=(
            Branch(
                elements=(
                    Seg(classes=(ck,), n_lead=1, n_trail=0),
                    Seg(classes=(cx,)),
                ),
                anchored_start=False,
                anchored_end=False,
            ),
        ),
        always=False,
    )

    def oracle(pi: int, value: bytes) -> bool:
        if pi == 0:  # A: an 'x' with a 'k' somewhere at/after the next byte
            return re.search(rb"x.*(?=k)", value) is not None
        return re.search(rb"kx", value) is not None  # B

    values = [b"xk", b"kx", b"x123k", b"k123x", b"xxxx", b"kkkk", b"axkb", b"akxb"]
    for order in ([0, 1], [1, 0]):
        block = build_segment_block([[plan_a, plan_b][i] for i in order])
        for value in values:
            data = np.zeros((1, 8), dtype=np.uint8)
            data[0, : len(value)] = np.frombuffer(value, dtype=np.uint8)
            lengths = np.asarray([len(value)], dtype=np.int32)
            hits = np.asarray(
                match_segment_block(block.kernel, block.spec, data, lengths)
            )
            for col, pi in enumerate(order):
                assert bool(hits[0, col]) == oracle(pi, value), (
                    order,
                    pi,
                    value,
                )


# -- runs past MAX_SEG_LEN: chained pieces (ISSUE 40) -------------------------
#
# A run of more than MAX_SEG_LEN real positions is cut into adjacent
# pieces, full ones from the left and the remainder last; the chain joins
# them at their exact offsets. Each run length builds ONE block of all
# its variants (one jit a length), and every (length, variant) is a case
# of its own against Python ``re`` on the length's whole row set.

_WORDY = "select_table_name_from_information_schema_tables_where_table_schema_is_not_null"
_OTHER = "wp_content_plugins_akismet_anti_spam_includes_class_akismet_admin_widget_php_x"
RUN_LENGTHS = [24, 25, 26, 47, 48, 49, 50, 72, 73]
# variant -> pattern of one run ``a`` (and a second run ``b`` for the gaps)
RUN_VARIANTS = {
    "plain": lambda a, b: a,
    "nocase": lambda a, b: f"(?i:{a})",
    "wordb_head": lambda a, b: rf"\b{a}",
    "wordb_tail": lambda a, b: rf"{a}\b",
    "wordb_both": lambda a, b: rf"\b{a}\b",
    "start": lambda a, b: f"^{a}",
    "end": lambda a, b: f"{a}$",
    "start_end": lambda a, b: f"^{a}$",
    "bounded_gap": lambda a, b: f"{a}.{{2,5}}{b}",
    "unbounded_gap": lambda a, b: f"{a}[^/]*{b}",
}


def _left_cut(n: int) -> tuple[int, ...]:
    from coraza_kubernetes_operator_tpu.compiler.segments import MAX_SEG_LEN

    return (MAX_SEG_LEN,) * (n // MAX_SEG_LEN) + ((n % MAX_SEG_LEN,) if n % MAX_SEG_LEN else ())


def _mutations(witness: bytes) -> list[bytes]:
    """The witness at offset 0, mid-row and after a word byte; cut short
    at every length; and with one byte dropped, doubled or changed at
    every offset: a piece joined one position off, a run matched without
    one of its pieces, or a near miss inside any one piece shows on one."""
    out = [witness, b"~" + witness, witness + b"~", b"zz " + witness + b" zz",
           b"x" + witness, witness + b"x", witness + b"_", b"9" + witness + b" ",
           b" " + witness + b"9", b"(" + witness.upper() + b")", witness.title()]
    out += [witness[:k] for k in range(len(witness))]
    for k in range(len(witness)):
        other = b"#" if witness[k : k + 1] != b"#" else b"%"
        out.append(witness[:k] + witness[k + 1 :])
        out.append(witness[:k] + witness[k : k + 1] + witness[k:])
        out.append(witness[:k] + other + witness[k + 1 :])
    return out


def _pack(rows: list[bytes], max_len: int):
    data = np.zeros((len(rows), max_len), dtype=np.uint8)
    lengths = np.zeros(len(rows), dtype=np.int32)
    for i, r in enumerate(rows):
        data[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
        lengths[i] = len(r)
    return data, lengths


@functools.lru_cache(maxsize=None)
def _run_block(n: int):
    """(patterns, plans, rows, hits, kernel width) of every variant at
    run length ``n``: one block, one jit."""
    a, b = _WORDY[:n], _OTHER[:n]
    pats = {name: make(a, b) for name, make in RUN_VARIANTS.items()}
    plans = {name: plan_segments(parse_regex(p)) for name, p in pats.items()}
    wa, wb = a.encode(), b.encode()
    rows = [b"", *_mutations(wa)]
    for gap in (b"", b"a", b"ab", b"abcde", b"abcdef", b"a/b", b"?x=1&y="):
        rows.append(wa + gap + wb)
    rows += [wb + wa, wb + b"ab" + wa]
    # the two-run witness with a near miss inside each piece of each run
    two = wa + b"abc" + wb
    for start in (0, n + 3):
        for k in range(start, start + n, 12):
            rows.append(two[:k] + b"#" + two[k + 1 :])
    max_len = max(len(r) for r in rows) + 3
    # hits that end at the row's last byte: the buffer has no slack there
    rows += [b"." * (max_len - n) + wa, b" " * (max_len - len(two)) + two,
             b"." * (max_len - n - 1) + wa + b"x"]
    data, lengths = _pack(rows, max_len)
    block = build_segment_block(list(plans.values()))
    hits = np.asarray(match_segment_block(block.kernel, block.spec, data, lengths))
    return pats, plans, rows, hits, block.spec.w


@pytest.mark.parametrize("variant", list(RUN_VARIANTS))
@pytest.mark.parametrize("n", RUN_LENGTHS)
def test_long_runs_match_python_re(n, variant):
    from coraza_kubernetes_operator_tpu.compiler.segments import MAX_SEG_LEN, Seg

    pats, plans, rows, hits, w = _run_block(n)
    plan = plans[variant]
    assert plan is not None and len(plan.branches) == 1
    segs = [el for el in plan.branches[0].elements if isinstance(el, Seg)]
    # ``.{2,5}`` is two positions of the first run and a gap of 0 to 3
    runs = {"bounded_gap": (n + 2, n), "unbounded_gap": (n, n)}.get(variant, (n,))
    assert tuple(s.n_real for s in segs) == sum((_left_cut(r) for r in runs), ())
    assert plan.splits == sum(r > MAX_SEG_LEN for r in runs)
    # the contexts ride the end pieces only, and the kernel is no wider
    # than one piece and its contexts
    assert all(s.n_lead == 0 for s in segs[1:]) and all(s.n_trail == 0 for s in segs[:-1])
    assert w <= MAX_SEG_LEN + 2
    oracle = re.compile(pats[variant].encode())
    want = [oracle.search(r) is not None for r in rows]
    assert any(want) and not all(want)
    col = list(RUN_VARIANTS).index(variant)
    for i, r in enumerate(rows):
        assert bool(hits[i, col]) == want[i], (pats[variant], r)


_PATH = "/wp-content/plugins/akismet-anti-spam/includes/class-akismet-admin-widget.php"
_L26, _L30 = _PATH[:26], _WORDY[:30]
_PERIODIC = "ab" * 15  # pieces "ab" * 12 / "ab" * 3: the second also matches inside the first
_P1, _P2 = _PERIODIC[:24].encode(), _PERIODIC[24:].encode()

# (id, pattern, case-insensitive, real positions of the first branch's Segs, witnesses, extra rows)
SPLIT_CASES = [
    ("path26", re.escape(_L26), False, (24, 2), [_L26], []),
    ("path26_nocase", re.escape(_L26), True, (24, 2), [_L26, _L26.upper(), _L26.title()], []),
    ("path72", re.escape(_PATH[:72]), False, (24, 24, 24), [_PATH[:72]], []),
    ("two_long_runs_and_a_class_gap", re.escape(_L26) + r"[^/]*" + _L30, False,
     (24, 2, 24, 6), [_L26 + _L30, _L26 + "?x=1&y=" + _L30],
     [(_L26 + "a/b" + _L30).encode(), (_L30 + _L26).encode()]),
    ("two_long_runs_and_a_counted_gap", re.escape(_L26) + r".{2,5}" + _L30, False,
     (24, 4, 24, 6), [_L26 + "ab" + _L30, _L26 + "abcde" + _L30],
     [(_L26 + "a" + _L30).encode(), (_L26 + "abcdef" + _L30).encode()]),
    ("periodic", _PERIODIC, False, (24, 6), [_PERIODIC],
     # each piece, but not adjacent; adjacent but shifted by one; one
     # period short (piece 1 at 0 and piece 2 at 22, not at 24)
     [_P1 + b"x" + _P2, _P1 + _P1[:5], _P2 + _P1, _P1 + _P2[1:], b"ab" * 14, b"ab" * 14 + b"a",
      b"b" + b"ab" * 14 + b"a", b"ab" * 16, b"ba" * 15 + b"b", _P1 + b"a" + _P2]),
    ("input_ends_inside_the_last_piece", re.escape(_L26) + r"\d", False, (24, 3),
     [_L26 + "7"], [_L26.encode()[:k] for k in (13, 24, 25)] + [_L26.encode()]),
    ("class_run_of_30", r"[0-9a-f]{30}", False, (24, 6), ["0123456789abcdef" * 2],
     [b"0123456789abcde" * 2, b"0123456789abcdeg" + b"0123456789abcde", b"f" * 29, b"f" * 30,
      b"f" * 23 + b"g" + b"f" * 6 + b"g" + b"f" * 29]),
    ("class_run_of_32_anchored", r"^[0-9a-f]{32}$", False, (24, 8), ["0123456789abcdef" * 2],
     [b"f" * 31, b"f" * 33, b"f" * 24 + b"g" + b"f" * 7, b"f" * 32 + b" "]),
    ("counted_run_past_the_cap", r"x[0-9]{26,28}y", False, (24, 3, 1),
     ["x" + "1" * 26 + "y", "x" + "1" * 28 + "y"],
     [b"x" + b"1" * 25 + b"y", b"x" + b"1" * 29 + b"y"]),
    ("gap_first_branch", r"[0-9]*" + re.escape(_L26), False, (24, 2), [_L26, "42" + _L26], []),
    ("alternation_of_long_runs", f"(?:{_WORDY[:40]}|{_OTHER[:30]})", False, (24, 16),
     [_WORDY[:40], _OTHER[:30]], [(_WORDY[:24] + _OTHER[24:30]).encode()]),
]


@pytest.mark.parametrize("case", SPLIT_CASES, ids=[c[0] for c in SPLIT_CASES])
def test_split_runs_match_python_re(case):
    """Shapes of a split run other than one literal: class runs, counted
    runs, a gap-first branch, a periodic text whose pieces also match one
    position off; each a block of its own."""
    from coraza_kubernetes_operator_tpu.compiler.segments import MAX_SEG_LEN, Seg

    _name, pat, ci, pieces, witnesses, extra = case
    plan = plan_segments(parse_regex(pat, case_insensitive=ci))
    assert plan is not None and plan.splits >= 1
    segs = [el for el in plan.branches[0].elements if isinstance(el, Seg)]
    assert tuple(s.n_real for s in segs) == pieces
    assert all(s.n_real <= MAX_SEG_LEN for br in plan.branches for s in br.elements
               if isinstance(s, Seg))
    block = build_segment_block([plan])

    rows = [b"", *extra]
    for w in witnesses:
        rows += _mutations(w.encode())
    data, lengths = _pack(rows, 8 * -(-(max(len(r) for r in rows) + 1) // 8))
    hits = np.asarray(match_segment_block(block.kernel, block.spec, data, lengths))
    oracle = re.compile(pat.encode(), re.IGNORECASE if ci else 0)
    want = [oracle.search(r) is not None for r in rows]
    assert any(want) and not all(want)
    for i, r in enumerate(rows):
        assert bool(hits[i, 0]) == want[i], (pat, r)


def test_split_pieces_carry_the_contexts_on_the_ends_only():
    from coraza_kubernetes_operator_tpu.compiler.segments import Seg

    plan = plan_segments(parse_regex(r"\b" + _WORDY[:50] + r"\b"))
    (branch,) = plan.branches
    assert plan.splits == 1 and all(isinstance(el, Seg) for el in branch.elements)
    assert [(s.n_lead, s.n_real, s.n_trail) for s in branch.elements] == [
        (1, 24, 0), (0, 24, 0), (0, 2, 1)]
    # a run of MAX_SEG_LEN is one piece, as before
    whole = plan_segments(parse_regex(_WORDY[:24]))
    assert whole.splits == 0 and len(whole.branches[0].elements) == 1


def test_rules_of_one_template_share_their_remainder():
    """Why the cut is from the left: path patches that end alike bring
    one conv column each (their first 24 bytes) and share the remainder's
    column and the one chain suffix it makes."""
    from coraza_kubernetes_operator_tpu.ops.segment import conv_n2_cols

    pats = [rf"(?i:/{a}/{b}/{c}\.php)" for a, b, c in
            [("abcdef", "ghijklmn", "opqrs"), ("tuvwxy", "zabcdefg", "hijkl"),
             ("mnopqr", "stuvwxyz", "abcde"), ("fghijk", "lmnopqrs", "tuvwx")]]
    plans = [plan_segments(parse_regex(p)) for p in pats]
    assert all(p.splits == 1 for p in plans)
    spec = build_segment_block(plans).spec
    assert spec.n_seg == len(pats) + 1 and conv_n2_cols(spec) == len(pats) + 1
    assert len({(prog[1:], a_end) for _g, prog, _s, a_end in spec.branches}) == 1


def test_a_run_of_more_pieces_than_max_elements_stays_dense():
    from coraza_kubernetes_operator_tpu.compiler.segments import MAX_ELEMENTS, MAX_SEG_LEN

    fits = "q" * (MAX_SEG_LEN * MAX_ELEMENTS)
    plan = plan_segments(parse_regex(fits))
    assert plan is not None and len(plan.branches[0].elements) == MAX_ELEMENTS
    assert plan_segments(parse_regex(fits + "q")) is None
    # and pieces count against the branch's other elements
    assert plan_segments(parse_regex(r"a\d+" + "q" * (MAX_SEG_LEN * (MAX_ELEMENTS - 2)))) is not None
    assert plan_segments(parse_regex(r"a\d+b" + "q" * (MAX_SEG_LEN * (MAX_ELEMENTS - 2)))) is None


# What each rule text's groups plan to, pinned against the parent commit
# (830c183, read there with the same digest): a text with no run past
# MAX_SEG_LEN must give the plans it gave before ISSUE 40, object for
# object, so its matcher executables are the ones that were measured.
_CONFIGS = "wafbench/configs"
_TENANT = _CONFIGS + "/operator-sample-tenants32/rules/text-{}.conf"
# (id, rule file, groups, groups with no plan, digest of the parent's plans)
UNSPLIT_TEXTS = [
    ("sample", _CONFIGS + "/operator-sample/rules.conf", 2, 0, "4a11e33d779086a2"),
    ("tenant-a", _TENANT.format("a"), 2, 0, "4a11e33d779086a2"),
    ("tenant-b", _TENANT.format("b"), 1, 0, "f5e61c29948e9ab2"),
    ("tenant-c", _TENANT.format("c"), 12, 0, "175e5cca748931ff"),
    ("tenant-d", _TENANT.format("d"), 5, 1, "055908cb9a5cef47"),
]


@pytest.mark.parametrize("case", UNSPLIT_TEXTS, ids=[c[0] for c in UNSPLIT_TEXTS])
def test_texts_with_no_long_run_plan_as_the_parent_did(case):
    import hashlib
    from pathlib import Path

    from coraza_kubernetes_operator_tpu.compiler.automata_plan import plan_automata
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules

    _name, rel, n_groups, n_dense, digest = case
    crs = compile_rules((Path(__file__).resolve().parents[1] / rel).read_text())
    plans = [plan_segments(g.dfa.ast) for g in crs.groups]
    assert (len(plans), sum(p is None for p in plans)) == (n_groups, n_dense)
    assert all(p.splits == 0 for p in plans if p is not None)
    shape = [None if p is None else (p.branches, p.always) for p in plans]
    assert hashlib.sha256(repr(shape).encode()).hexdigest()[:16] == digest
    assert sum(t.splits for t in plan_automata(crs).tiers) == 0


def test_crs_lite_has_fifteen_split_groups():
    """Fifteen of crs-lite's 49 groups outside the conv tier were there
    for a literal past MAX_SEG_LEN and nothing else; the 401-byte one
    needs 17 pieces and stays dense."""
    from pathlib import Path

    from coraza_kubernetes_operator_tpu.compiler.automata_plan import plan_automata
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules_cached
    from coraza_kubernetes_operator_tpu.compiler.segments import MAX_SEG_LEN, Seg
    from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text

    cache = str(Path(__file__).resolve().parent / ".crs_cache")
    crs = compile_rules_cached(load_ruleset_text(), cache)
    tiers = plan_automata(crs).tiers
    assert sum(1 for t in tiers if t.splits) == 15 and sum(t.splits for t in tiers) == 26
    assert sum(1 for t in tiers if t.kind != "segment") == 34
    assert all(t.kind == "segment" for t in tiers if t.splits)
    longest = 0
    for g in crs.groups:
        plan = plan_segments(g.dfa.ast)
        for br in plan.branches if plan is not None else ():
            longest = max([longest, *(el.n_real for el in br.elements if isinstance(el, Seg))])
    assert longest == MAX_SEG_LEN  # the kernel is no wider than it was
