"""DFA bank kernel vs per-DFA reference scanner."""

import random

import jax.numpy as jnp
import numpy as np

from coraza_kubernetes_operator_tpu.compiler import (
    compile_regex_dfa,
    literal_dfa,
    pm_dfa,
)
from coraza_kubernetes_operator_tpu.ops import scan_dfa_bank, stack_dfas

PATTERNS = [
    ("rx", r"(?i:(\b(select|union|insert|update|delete|drop)\b.*\b(from|into|where|table)\b))"),
    ("rx", r"(?i:<script[^>]*>)"),
    ("rx", "^/admin"),
    ("rx", r"\bor\b\s*['\"]?\d+['\"]?\s*=\s*['\"]?\d+"),
    ("rx", "passwd$"),
    ("rx", "a*"),  # always-match
    ("lit", b"evilmonkey"),
    ("pm", [b"sleep", b"benchmark", b"waitfor"]),
]

CORPUS = [
    b"",
    b"GET /index.html",
    b"/admin/panel",
    b"x/admin",
    b"select * from users",
    b"SELECT a FROM b",
    b"selections from x",
    b"<script>alert(1)</script>",
    b"benchmark(100)",
    b"evilmonkey was here",
    b"or 1=1",
    b"for 1=1",
    b"/etc/passwd",
    b"passwd file",
    b"a" * 80,
]


def _bank():
    dfas = []
    for kind, arg in PATTERNS:
        if kind == "rx":
            dfas.append(compile_regex_dfa(arg))
        elif kind == "lit":
            dfas.append(literal_dfa(arg))
        else:
            dfas.append(pm_dfa(arg))
    return dfas, stack_dfas(dfas)


def test_scan_matches_reference():
    dfas, bank = _bank()
    rng = random.Random(7)
    fuzz = [
        bytes(rng.choice(b"abcdefor1=' <>script/untilfwm") for _ in range(rng.randrange(0, 60)))
        for _ in range(100)
    ]
    cases = CORPUS + fuzz
    max_len = 96
    n = len(cases)
    data = np.zeros((n, max_len), dtype=np.uint8)
    lengths = np.zeros(n, dtype=np.int32)
    for i, c in enumerate(cases):
        c = c[:max_len]
        data[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
        lengths[i] = len(c)

    matched = np.asarray(scan_dfa_bank(bank, jnp.asarray(data), jnp.asarray(lengths)))
    for i, c in enumerate(cases):
        for g, dfa in enumerate(dfas):
            assert matched[i, g] == dfa.search(c[:max_len]), (c, PATTERNS[g])


def test_scan_zero_length_rows():
    dfas, bank = _bank()
    data = jnp.zeros((4, 16), dtype=jnp.uint8)
    lengths = jnp.zeros(4, dtype=jnp.int32)
    matched = np.asarray(scan_dfa_bank(bank, data, lengths))
    for g, dfa in enumerate(dfas):
        assert (matched[:, g] == dfa.search(b"")).all()


def _random_batch(n, max_len, seed=3):
    rng = random.Random(seed)
    data = np.zeros((n, max_len), dtype=np.uint8)
    lengths = np.zeros(n, dtype=np.int32)
    for i in range(n):
        c = bytes(
            rng.choice(b"abcdefor1=' <>script/untilfwm")
            for _ in range(rng.randrange(0, max_len))
        )
        data[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
        lengths[i] = len(c)
    return data, lengths


def test_take_and_gather_formulations_agree():
    from coraza_kubernetes_operator_tpu.ops.dfa import (
        scan_dfa_bank_gather,
        scan_dfa_bank_take,
    )

    _, bank = _bank()
    data, lengths = _random_batch(64, 48)
    m_take = np.asarray(
        scan_dfa_bank_take(bank, jnp.asarray(data), jnp.asarray(lengths))
    )
    m_gather = np.asarray(
        scan_dfa_bank_gather(bank, jnp.asarray(data), jnp.asarray(lengths))
    )
    assert (m_take == m_gather).all()


def test_matmul_scan_xla_miscompile_guard():
    """Regression guard for the XLA bug that forced the `take` formulation.

    A one-hot @ table matmul *inside* ``lax.scan`` returns wrong results at
    batch sizes ~4000-5000 (bisected: wrong at 4000-5000, correct at 3072 and
    8192; identical on XLA:CPU and XLA:TPU; correct when the identical step
    runs outside the loop). The shipped take-scan must stay correct at those
    shapes. This exercises B=4096 directly.
    """
    from coraza_kubernetes_operator_tpu.ops.dfa import scan_dfa_bank_take

    dfas, bank = _bank()
    data, lengths = _random_batch(4096, 24, seed=5)
    matched = np.asarray(
        scan_dfa_bank_take(bank, jnp.asarray(data), jnp.asarray(lengths))
    )
    for i in (0, 1, 17, 4095):
        raw = bytes(data[i, : lengths[i]])
        for g, dfa in enumerate(dfas):
            assert matched[i, g] == dfa.search(raw), (raw, PATTERNS[g])
    # spot-check aggregate: every column equals the oracle column
    for g, dfa in enumerate(dfas):
        ref = np.fromiter(
            (dfa.search(bytes(data[i, : lengths[i]])) for i in range(0, 4096, 37)),
            dtype=bool,
        )
        assert (matched[::37, g] == ref).all(), PATTERNS[g]
