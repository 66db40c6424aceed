"""Per-tier matcher executable: dense-DFA blocks (nfa, dfa-hot and
prefilter banks) of the serving engine that no fused flat-slot bin
covers, each of which is a kernel of its own in every matcher launch
(``/waf/v1/stats`` ``automata.per_bank_kernels``, after warm-up). 0
says every block rode a bin. A program from before the bins gives
nothing to read."""

SOURCE = "program_counter"


def read(ctx):
    return ctx["setup"].get("automata", {}).get("per_bank_kernels")
