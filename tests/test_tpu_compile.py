"""Compile the served path's device programs for a DESCRIBED v5e chip.

No chip is attached to the test machine, but the TPU compiler is
installed and compiles for a topology that is described, not present
(``jax.experimental.topologies``). That catches what interpret mode and
the CPU backend cannot: a Pallas kernel Mosaic will not lower, a kernel
over its scoped-VMEM limit, a program that does not fit HBM. Nothing
runs, so these say nothing about results or times — ``chip_smoke.py`` on
a real chip does.

Shapes are the ones full crs-lite produces: the banks of the engine
built on ``ftw/rules/crs-lite`` at the smoke's sidecar window (32 unique
rows x 512) and at a large batch (4096 rows x the 2048 Pallas width
cap), the promotion canary's whole per-tier matcher (16 x 32), the
whole matcher at the widest rows the Pallas kernels take (32 x 2048: a
window of bodied API requests, wafbench's ``crs-lite-pl2-bodies``), and
the post stage.

Dispatch in ``ops/`` asks ``jax.default_backend()`` — which is the CPU
here — so the module fixture answers "tpu" for the duration of this
file: the banks are built with their on-chip dtypes and the dispatchers
take their on-chip branch. Everything that touches the TPU library
happens inside fixtures of THIS file (never at import, never in
conftest): only the xdist worker that is handed this file loads it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

ROWS_WINDOW, WIDTH_WINDOW = 32, 512  # the chip smoke's one sidecar window
ROWS_BATCH, WIDTH_MAX = 4096, 2048  # a large batch x the Pallas width cap
ROWS_CANARY, WIDTH_CANARY = 16, 32  # engine/waf.py:warmup_request's window
ROWS_BODIES, WIDTH_BODIES = 32, 2048  # wafbench crs-bodies.api-2k-c1's one window shape


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def on_chip(one_chip):
    """Steer backend-dependent dispatch to its on-chip branch and keep
    the persistent compile cache out of it (an executable compiled for a
    described chip is written but cannot be read back without one)."""
    from jax._src import compilation_cache as cc

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def crs_lite(on_chip):
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text

    return WafEngine(load_ruleset_text())


@pytest.fixture(scope="module")
def operand(one_chip):
    """``operand(shape, dtype)`` -> an array of that shape on the chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def described(operand):
    """``described(tree)`` -> the same pytree as shapes on the chip."""
    return lambda tree: jax.tree_util.tree_map(
        lambda x: operand(np.shape(x), np.result_type(x)), tree
    )


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# (bin, rows, width): each fused bin of the default crs-lite plan at
# the sidecar window, the bodies window and the large batch. The
# dispatcher must pick the Pallas kernel (one tpu_custom_call), never the
# XLA fallback. "flat<i>" is the plan's i-th fused bin: every dense-DFA
# block of crs-lite (nfa buckets, dfa-hot blocks, prefilter
# approximations) rides one of FLAT_BINS bins. A block the flat planner
# leaves out has no kernel: ``outside`` below compiles its XLA scan.
FLAT_BINS = 2
KERNEL_CASES = [
    ("flat0", ROWS_WINDOW, WIDTH_WINDOW),
    ("flat0", ROWS_BATCH, WIDTH_MAX),
    ("flat0", ROWS_BODIES, WIDTH_BODIES),
    ("flat1", ROWS_WINDOW, WIDTH_WINDOW),
    ("flat1", ROWS_BODIES, WIDTH_BODIES),
    ("flat1", ROWS_BATCH, WIDTH_MAX),
]


@pytest.mark.parametrize("family,rows,width", KERNEL_CASES)
def test_pallas_kernel_compiles_for_v5e(crs_lite, described, operand, family, rows, width):
    from coraza_kubernetes_operator_tpu.ops.dfa_flat import scan_flat_bank

    # ops/dfa_flat.py:_scan_flat_pallas — every dense-DFA block of
    # crs-lite rides one of the fused flat bins.
    model = crs_lite.model
    assert len(model.flat_banks) == FLAT_BINS and model.banks == []
    assert len(model.flat_covered) == len(model.dense_blocks)
    bank = model.flat_banks[int(family[len("flat"):])]
    pipes = sorted(set(bank.seg_pipes))
    text = _compile(
        lambda b, d, n: scan_flat_bank(b, {p: (d, n) for p in pipes}),
        described(bank), operand((rows, width), jnp.uint8), operand((rows,), jnp.int32),
    )
    assert text.count("tpu_custom_call") == 1, "dispatch fell back off the Pallas kernel"


def test_post_stage_compiles_for_v5e(crs_lite, described, operand):
    from coraza_kubernetes_operator_tpu.models.slab import post_slab_words
    from coraza_kubernetes_operator_tpu.models.waf_model import stage_executable

    # As the engine dispatches it: under the module name a trace
    # reduction finds the post stage by (wafbench: "eval_post").
    eval_post_tiered = stage_executable("eval_post", f"{ROWS_WINDOW}x{WIDTH_WINDOW}")
    model = crs_lite.model
    packed = (int(model.e_lg.shape[0]) + 7) // 8
    n_vars = crs_lite.compiled.numvars.n_vars
    # The window's post slab: 512 pair rows, 16 requests, 256 cached rows.
    layout = (((512, 256),), 16, n_vars, packed)
    text = (
        eval_post_tiered.lower(
            described(model),
            (operand((ROWS_WINDOW, packed), jnp.uint8),),
            operand((post_slab_words(layout),), jnp.int32),
            max_phase=2,
            layout=layout,
        )
        .compile()
        .as_text()
    )
    assert f"HloModule jit_cko_eval_post_{ROWS_WINDOW}x{WIDTH_WINDOW}" in text


def test_canary_matcher_compiles_for_v5e(crs_lite, described, operand):
    """The whole per-tier matcher executable — transforms, the XLA conv
    tier of ops/segment.py, and every flat bin in one program — at
    the promotion canary's shape, which every cold sidecar compiles
    before it may serve from the device."""
    from coraza_kubernetes_operator_tpu.models.slab import match_slab_shape
    from coraza_kubernetes_operator_tpu.models.waf_model import stage_executable

    model = crs_lite.model
    h = max(1, len(crs_lite._host_pipelines))
    u, width = ROWS_CANARY, WIDTH_CANARY
    match_tier_packed = stage_executable("match", f"{u}x{width}")
    compiled = match_tier_packed.lower(
        described(model),
        operand(match_slab_shape(u, width, h), jnp.uint8),
        mask=None,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == len(model.flat_banks) == FLAT_BINS
    # The names a device trace prints: the module by role and window
    # shape (only the post stage's holds "eval_post"), every Pallas
    # kernel by family and bank, not by XLA's running counter.
    assert f"HloModule jit_cko_match_{u}x{width}" in text and "eval_post" not in text
    for i in range(len(model.flat_banks)):
        assert f"%cko_flat_bin{i}" in text, f"cko_flat_bin{i}"
    # It has to fit beside the model's tables in one v5e's 16 GB.
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**30


@pytest.mark.parametrize("rows,width", [(ROWS_BODIES, WIDTH_BODIES)])
def test_long_matcher_compiles_for_v5e(crs_lite, described, operand, rows, width):
    """The whole matcher at width 2048, where a window holds a body of
    1 to 2 KiB: every dense-DFA block still rides a flat bin's Pallas
    kernel (the bins sit at the edge of their VMEM plan there, one
    ``int32`` data tile a pipeline; ``_PALLAS_MAX_LEN``), none falls to
    the XLA scan, and the program fits beside the tables."""
    from coraza_kubernetes_operator_tpu.models.slab import match_slab_shape
    from coraza_kubernetes_operator_tpu.models.waf_model import stage_executable

    model = crs_lite.model
    h = max(1, len(crs_lite._host_pipelines))
    compiled = stage_executable("match", f"{rows}x{width}").lower(
        described(model),
        operand(match_slab_shape(rows, width, h), jnp.uint8),
        mask=None,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == len(model.flat_banks) == FLAT_BINS
    assert f"HloModule jit_cko_match_{rows}x{width}" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**30


def test_canary_matcher_with_a_block_outside_every_bin_compiles_for_v5e(on_chip, described, operand):
    """The model of ``tests/test_dense_blocks.py`` whose wide DFA no bin
    holds: its canary matcher is one Pallas call a flat bin and, for the
    block outside them, the XLA gather scan of ``ops/dfa.py`` under
    ``cko.dense``: no kernel of its own on the chip either."""
    from test_dense_blocks import OUTSIDE_RULES

    from coraza_kubernetes_operator_tpu.compiler.automata_plan import plan_automata
    from coraza_kubernetes_operator_tpu.compiler.ruleset import compile_rules
    from coraza_kubernetes_operator_tpu.models.slab import match_slab_shape
    from coraza_kubernetes_operator_tpu.models.waf_model import build_model, stage_executable

    crs = compile_rules(OUTSIDE_RULES)
    model = build_model(crs, plan_automata(crs, prefilter_enabled=False))
    assert len(model.banks) == 1 and len(model.flat_banks) == 1
    u, width = ROWS_CANARY, WIDTH_CANARY
    text = (
        stage_executable("match", f"{u}x{width}")
        .lower(described(model), operand(match_slab_shape(u, width, 1), jnp.uint8), mask=None)
        .compile()
        .as_text()
    )
    assert text.count("tpu_custom_call") == len(model.flat_banks) == 1
    assert "%cko_flat_bin0" in text and "/cko.dense/" in text


# --- the widest bin a custom feed makes (PR 37) ------------------------------
#
# A feed of small dense rules on one pipeline (the DFAs here are those
# of wafbench's ``crs-lite-pl2-custom5k`` path patches, 27 states each,
# which the engine itself serves from the conv tier) fills bins to the planner's
# budget: 128 groups, 3,456 slots, 14.2 MB on the estimator where
# crs-lite's widest bin holds 768 slots. The planner sizes a bin for
# the widest buffer the kernel takes, so it has to compile there too.


@pytest.fixture(scope="module")
def feed_bin(on_chip):
    from coraza_kubernetes_operator_tpu.compiler import compile_regex_dfa
    from coraza_kubernetes_operator_tpu.ops.dfa_flat import (
        _layout_stats,
        build_flat_bank,
        plan_flat_bins,
    )
    from wafbench.tools.freeze_custom import feed_rules

    dfas = [compile_regex_dfa(r["pattern"]) for r in feed_rules(400, 37)
            if r["template"] == "a"]
    bins, rejected = plan_flat_bins([(0, 0, dfas)])
    assert not rejected and len(bins) == 2
    return build_flat_bank(max(bins, key=lambda bn: _layout_stats(bn)[0]))


@pytest.mark.parametrize("rows,width", [(ROWS_WINDOW, WIDTH_WINDOW), (ROWS_BODIES, WIDTH_BODIES)])
def test_the_widest_bin_of_a_custom_feed_compiles_for_v5e(feed_bin, described, operand,
                                                          rows, width):
    from coraza_kubernetes_operator_tpu.ops.dfa_flat import scan_flat_bank

    assert (feed_bin.n_slots, feed_bin.n_groups) == (3456, 128)
    text = _compile(
        lambda b, d, n: scan_flat_bank(b, {0: (d, n)}),
        described(feed_bin), operand((rows, width), jnp.uint8), operand((rows,), jnp.int32),
    )
    assert text.count("tpu_custom_call") == 1, "dispatch fell back off the Pallas kernel"


# --- the same path patches as chained conv pieces (ISSUE 40) -----------------


def test_split_path_patches_compile_as_one_conv_block_for_v5e(on_chip, described, operand):
    """400 literals of 26 bytes, 24 + 2 each: 400 first pieces under the
    one suffix their shared remainder makes, so the program is a few
    hundred lines whatever the feed's size, and its conv output fits."""
    from coraza_kubernetes_operator_tpu.compiler.re_parser import parse_regex
    from coraza_kubernetes_operator_tpu.compiler.segments import plan_segments
    from coraza_kubernetes_operator_tpu.ops.segment import (
        build_segment_block,
        conv_n2_cols,
        match_segment_block,
    )
    from wafbench.tools.freeze_custom import feed_rules

    rules = [r for r in feed_rules(1000, 37) if r["template"] == "a"]
    plans = [plan_segments(parse_regex(r["pattern"])) for r in rules]
    assert len(plans) == 400 and all(p is not None and p.splits == 1 for p in plans)
    block = build_segment_block(plans)
    assert conv_n2_cols(block.spec) == 401 and block.spec.w == 24
    compiled = match_segment_block.lower(
        described(block.kernel), block.spec,
        operand((ROWS_WINDOW, WIDTH_WINDOW), jnp.uint8), operand((ROWS_WINDOW,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "convolution" in text and text.count("conditional(") < 8
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
