"""Differential tests: conv-segment matcher vs Python ``re``.

The segment tier must be *exact* (compiler/segments.py's contract):
every pattern the decomposer accepts is replayed against Python ``re``
on randomized word soup plus targeted edge inputs, byte for byte.
"""

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
    assert sum(b.n_groups for b in model.banks) >= 1


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


def test_gapcls_cumsum_path_at_large_q():
    """Above _NCE_MATMUL_MAX_Q the NCE prefix sum must switch to the
    O(Q) cumsum (no [Q, Q] table — a request-triggerable multi-GB
    allocation on long-body buckets) and stay byte-exact vs Python re."""
    pats = [(r"<script[^>]*>", True), (r"select\b.+\bfrom", True)]
    plans = [plan_segments(parse_regex(p, case_insensitive=ci)) for p, ci in pats]
    block = build_segment_block(plans)

    from coraza_kubernetes_operator_tpu.ops import segment as seg_mod

    max_len = seg_mod._NCE_MATMUL_MAX_Q + 70  # q = max_len + 2 > threshold
    rng = random.Random(7)
    rows = [
        b"x" * max_len,
        # positives with the match DEEP in the buffer (past the 512
        # matmul/cumsum threshold) — must fit inside max_len
        (b"z" * 540) + b"<script src=a>" + b"y" * 20,
        b"select " + b"a" * 530 + b" from t",
        b"<script" + b">" * 1,  # short content, long bucket
        bytes(rng.randrange(32, 127) for _ in range(max_len)),
    ]
    assert all(len(c) <= max_len for c in rows[1:3])
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
