"""Wall-loop microbenchmark of the DFA-bank scan formulations.

Builds a synthetic bank of literal+regex DFAs via the real compiler path
(so t256/packed tables are consistent) and times the dispatched scan, the
XLA take-scan and the gather oracle. Timing is wall time over N
back-to-back calls on device-distinct inputs with one final block, so
async dispatch overlaps the calls as it does in serving.

Run: `python benchmarks/profile_scan.py` (TPU) or under the CPU conftest.
"""

import statistics
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp


def make_bank(n_groups: int):
    from coraza_kubernetes_operator_tpu.compiler import compile_regex_dfa, literal_dfa
    from coraza_kubernetes_operator_tpu.ops import stack_dfas

    dfas = []
    for i in range(n_groups):
        if i % 3 == 0:
            dfas.append(compile_regex_dfa(rf"(?i:attack{i}\s+x{i % 7})"))
        else:
            dfas.append(literal_dfa(f"needle{i}".encode(), case_insensitive=True))
    return stack_dfas(dfas)


def wall(fn, n=20):
    out = fn(0)
    jax.block_until_ready(out)
    # second warm round: first-round executables/allocator are ~4x slow
    jax.block_until_ready([fn(i) for i in range(4)])
    t0 = time.perf_counter()
    res = [fn(i) for i in range(n)]
    jax.block_until_ready(res)
    return (time.perf_counter() - t0) / n


def main():
    from coraza_kubernetes_operator_tpu.ops.dfa import (
        scan_dfa_bank,
        scan_dfa_bank_gather,
        scan_dfa_bank_take,
    )

    print("platform:", jax.default_backend())
    rng = np.random.default_rng(0)
    for (b, l, g) in [(4096, 64, 155), (1024, 256, 155), (4096, 64, 32)]:
        bank = make_bank(g)
        data = jnp.asarray(rng.integers(0, 256, size=(b, l), dtype=np.uint8))
        lengths = jnp.asarray(rng.integers(0, l + 1, size=(b,), dtype=np.int32))
        for name, fn in [
            ("dispatch", scan_dfa_bank),
            ("take", scan_dfa_bank_take),
            ("gather", scan_dfa_bank_gather),
        ]:
            t = wall(lambda i, f=fn: f(bank, data.at[0, 0].set(i % 250), lengths))
            print(
                f"B={b} L={l} G={g} S={bank.n_states} {name:9s}: "
                f"{t*1e3:8.2f} ms  ({b*l/t/1e6:8.1f} MB/s)"
            )


if __name__ == "__main__":
    main()
