"""Device scopes (observability/device_scopes.py, PR 41): the ``cko.``
names inside the matcher and post-stage executables, what a launch is
made of by scope, and a capture priced by scope.

The first half needs no JAX: the walker and the reduction on text and
events kept here. The second half compiles the promotion canary's
executables for two small rule sets on the CPU and holds the program to
the registry: every scope the model exercises is there, next to nothing
stands outside every scope, and the names change metadata and nothing
else (the optimized HLO's per-opcode instruction counts are those of a
build with ``jax.named_scope`` switched off).
"""

from __future__ import annotations

import ast
import collections
import contextlib
import json
from pathlib import Path

import pytest

from coraza_kubernetes_operator_tpu.observability import device_scopes as ds

PACKAGE = Path(ds.__file__).resolve().parents[1]

# -- the walker, on text kept here (no JAX) -----------------------------------------
#
# TPU-style layouts (tiles in parentheses), a tuple type with blanks, every
# opcode that is no operation, a fusion per way a fusion gets its scope, one
# computation per way a computation is reached or not.

_MATCH = "jit(cko_match_16x32)/jit(main)"
HLO = f"""HloModule jit_cko_match_16x32, is_scheduled=true, entry_computation_layout={{(u8[3,16,32]{{2,1,0}})->u8[16,1]{{1,0}}}}

FileNames
1 "/x/waf_model.py"

%region_or (a.1: pred[], b.1: pred[]) -> pred[] {{
  %a.1 = pred[] parameter(0)
  %b.1 = pred[] parameter(1)
  ROOT %or.9 = pred[] or(%a.1, %b.1), metadata={{op_name="{_MATCH}/cko.seg.final/reduce_or"}}
}}

%fused_conv (p0: bf16[16,34,8]) -> pred[16,34,4] {{
  %p0 = bf16[16,34,8]{{2,1,0:T(8,128)(2,1)}} parameter(0)
  %c2 = bf16[] constant(34)
  %conv.1 = bf16[16,34,4]{{2,1,0:T(8,128)(2,1)}} convolution(%p0, %p0), window={{size=3}}, dim_labels=b0f_0io->b0f, metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.conv/conv_general_dilated"}}
  ROOT %ge.1 = pred[16,34,4]{{2,1,0:T(8,128)(4,1)}} compare(%conv.1, %c2), direction=GE, metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.conv/ge"}}
}}

%fused_bare_root (p0.1: pred[16,34,4]) -> pred[16,34,4] {{
  %p0.1 = pred[16,34,4]{{2,1,0}} parameter(0)
  %and.3 = pred[16,34,4]{{2,1,0}} and(%p0.1, %p0.1), metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.suffix/b0.st017/and"}}
  %and.4 = pred[16,34,4]{{2,1,0}} and(%and.3, %p0.1), metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.suffix/b0.st017/and"}}
  %or.4 = pred[16,34,4]{{2,1,0}} or(%and.4, %p0.1), metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.embed/or"}}
  ROOT %copy.7 = pred[16,34,4]{{2,1,0}} copy(%or.4)
}}

%fused_nameless (p0.2: pred[16,34,4]) -> pred[16,34,4] {{
  %p0.2 = pred[16,34,4]{{2,1,0}} parameter(0)
  ROOT %not.1 = pred[16,34,4]{{2,1,0}} not(%p0.2)
}}

%chunk_cond (s.1: (s32[], pred[16,34,4])) -> pred[] {{
  %s.1 = (s32[], pred[16,34,4]{{2,1,0}}) parameter(0)
  %i.1 = s32[] get-tuple-element(%s.1), index=0
  %two = s32[] constant(2)
  ROOT %lt.1 = pred[] compare(%i.1, %two), direction=LT, metadata={{op_name="{_MATCH}/cko.seg.chunk/while/cond/lt"}}
}}

%chunk_body (s.2: (s32[], pred[16,34,4])) -> (s32[], pred[16,34,4]) {{
  %s.2 = (s32[], pred[16,34,4]{{2,1,0}}) parameter(0)
  %i.2 = s32[] get-tuple-element(%s.2), index=0
  %x.2 = pred[16,34,4]{{2,1,0}} get-tuple-element(%s.2), index=1
  %one = s32[] constant(1)
  %add.2 = s32[] add(%i.2, %one), metadata={{op_name="{_MATCH}/cko.seg.chunk/while/body/add"}}
  %inner.1 = pred[16,34,4]{{2,1,0}} fusion(%x.2), kind=kLoop, calls=%fused_bare_root, metadata={{op_name="{_MATCH}/cko.seg.chunk/while/body/jit(match_segment_block)/cko.seg.final/and"}}
  ROOT %t.2 = (s32[], pred[16,34,4]{{2,1,0}}) tuple(%add.2, %inner.1)
}}

%branch_skip (b.0: (pred[16,34,4])) -> pred[16,4] {{
  %b.0 = (pred[16,34,4]{{2,1,0}}) parameter(0)
  %f.0 = pred[] constant(false), metadata={{op_name="{_MATCH}/jit(match_segment_block)"}}
  ROOT %bc.0 = pred[16,4]{{1,0}} broadcast(%f.0), dimensions={{}}, metadata={{op_name="{_MATCH}/jit(match_segment_block)"}}
}}

%branch_run (b.1: (pred[16,34,4])) -> pred[16,4] {{
  %b.1 = (pred[16,34,4]{{2,1,0}}) parameter(0)
  %g.1 = pred[16,34,4]{{2,1,0}} get-tuple-element(%b.1), index=0
  %relayout.1 = pred[16,34,4]{{0,1,2:T(8,128)(4,1)S(1)}} copy(%g.1), metadata={{op_name="{_MATCH}/jit(match_segment_block)"}}
  %f.1 = pred[] constant(false)
  ROOT %red.1 = pred[16,4]{{1,0}} reduce(%relayout.1, %f.1), dimensions={{1}}, to_apply=%region_or, metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.final/cond/branch_1_fun/reduce_or"}}
}}

%old_true (b.2: pred[16,4]) -> pred[16,4] {{
  %b.2 = pred[16,4]{{1,0}} parameter(0)
  ROOT %not.2 = pred[16,4]{{1,0}} not(%b.2), metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.bucket/cond/branch_1_fun/not"}}
}}

%old_false (b.3: pred[16,4]) -> pred[16,4] {{
  ROOT %b.3 = pred[16,4]{{1,0}} parameter(0)
}}

%called (c.0: pred[16,4]) -> pred[16,4] {{
  %c.0 = pred[16,4]{{1,0}} parameter(0)
  ROOT %xor.5 = pred[16,4]{{1,0}} xor(%c.0, %c.0), metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.fold/xor"}}
}}

%never_reached (n.0: pred[16,4]) -> pred[16,4] {{
  %n.0 = pred[16,4]{{1,0}} parameter(0)
  ROOT %xor.6 = pred[16,4]{{1,0}} xor(%n.0, %n.0), metadata={{op_name="{_MATCH}/cko.dense/xor"}}
}}

ENTRY %main.1 (slab.1: u8[3,16,32]) -> u8[16,1] {{
  %slab.1 = u8[3,16,32]{{2,1,0:T(8,128)(4,1)}} parameter(0), metadata={{op_name="slab"}}
  %token.1 = token[] after-all()
  %zero = s32[] constant(0)
  %stray.1 = u8[3,16,32]{{2,1,0}} copy(%slab.1)
  %stray.2 = u8[3,16,32]{{2,1,0}} negate(%stray.1), metadata={{op_name="{_MATCH}/jit(helper)/neg"}}
  %view.1 = bf16[16,34,8]{{2,1,0:T(8,128)(2,1)}} bitcast(%slab.1), metadata={{op_name="{_MATCH}/cko.slab/bitcast_convert_type"}}
  %lower.1 = bf16[16,34,8]{{2,1,0:T(8,128)(2,1)}} fusion(%view.1), kind=kLoop, calls=%fused_nameless, metadata={{op_name="{_MATCH}/cko.transform/lowercase+urldecodeuni/jit(_where)/select_n"}}
  %conv_fusion = pred[16,34,4]{{2,1,0:T(8,128)(4,1)}} fusion(%lower.1), kind=kOutput, calls=%fused_conv
  %chain.1 = pred[16,34,4]{{2,1,0}} fusion(%conv_fusion), kind=kLoop, calls=%fused_bare_root
  %lost.1 = pred[16,34,4]{{2,1,0}} fusion(%chain.1), kind=kLoop, calls=%fused_nameless
  %prefetch.1 = (pred[16,34,4]{{2,1,0:S(1)}}, pred[16,34,4]{{2,1,0}}, u32[]{{:S(2)}}) copy-start(%lost.1)
  %copy.3 = pred[16,34,4]{{2,1,0:S(1)}} copy-done(%prefetch.1)
  %init.1 = (s32[], pred[16,34,4]{{2,1,0}}) tuple(%zero, %copy.3)
  %while.1 = (s32[], pred[16,34,4]{{2,1,0}}) while(%init.1), condition=%chunk_cond, body=%chunk_body, metadata={{op_name="{_MATCH}/cko.seg.chunk/while"}}
  %out.1 = pred[16,34,4]{{2,1,0}} get-tuple-element(%while.1), index=1
  %arg.1 = (pred[16,34,4]{{2,1,0}}) tuple(%out.1)
  %pick.1 = s32[] constant(1)
  %cond.1 = pred[16,4]{{1,0}} conditional(%pick.1, %arg.1, %arg.1), branch_computations={{%branch_skip, %branch_run}}, metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.final/cond"}}
  %flag.1 = pred[] constant(true)
  %cond.2 = pred[16,4]{{1,0}} conditional(%flag.1, %cond.1, %cond.1), true_computation=%old_true, false_computation=%old_false, metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.bucket/cond"}}
  %call.1 = pred[16,4]{{1,0}} call(%cond.2), to_apply=%called, metadata={{op_name="{_MATCH}/jit(match_segment_block)/cko.seg.fold"}}
  ROOT %pack.1 = u8[16,1]{{1,0}} convert(%call.1), metadata={{op_name="{_MATCH}/cko.stitch/jit(packbits)/convert_element_type"}}
}}
"""

# instruction -> scope path; everything else in HLO is no operation or not reached.
EXPECTED = {
    "stray.1": "unscoped",  # moved for an operation that has no scope, made from a parameter
    "stray.2": "unscoped",  # no component of its name starts with cko.
    "lower.1": "cko.transform/lowercase+urldecodeuni",  # its own name, one level beneath
    "conv_fusion": "cko.seg.conv",  # bare fusion: its root's
    "chain.1": "cko.seg.suffix/b0.st017",  # bare fusion, bare root: what most of it carries
    "lost.1": "cko.seg.suffix/b0.st017",  # nothing in it carries a name: what it was made from
    "prefetch.1": "cko.seg.chunk",  # a value the compiler moves: what it was moved for,
    "copy.3": "cko.seg.chunk",  # ... through the tuple, the loop
    "while.1": "cko.seg.chunk",
    "lt.1": "cko.seg.chunk",  # the condition, once
    "add.2": "cko.seg.chunk",  # the body, once whatever its trips
    "inner.1": "cko.seg.final",  # the innermost scope of a nested name
    "cond.1": "cko.seg.final",
    "bc.0": "cko.seg.final",  # every branch; a bare operation of a branch: its conditional's
    "relayout.1": "cko.seg.final",  # a branch's operand, moved for the reduction
    "red.1": "cko.seg.final",  # ... and not its reducer
    "cond.2": "cko.seg.bucket",
    "not.2": "cko.seg.bucket",  # true_computation / false_computation
    "call.1": "cko.seg.fold",
    "xor.5": "cko.seg.fold",  # the inside of a call
    "pack.1": "cko.stitch",
}


def test_the_walker_counts_what_a_trace_would_show_each_once_under_its_scope():
    assert ds.table(HLO) == EXPECTED


def test_count_sums_by_the_registrys_names():
    got = ds.count(HLO)
    assert got["total"] == len(EXPECTED) and got["unscoped"] == 2
    assert got["by_scope"] == {
        "cko.seg.bucket": 2, "cko.seg.chunk": 5, "cko.seg.conv": 1, "cko.seg.final": 5,
        "cko.seg.fold": 2, "cko.seg.suffix": 2, "cko.stitch": 1, "cko.transform": 1,
    }
    assert set(got["by_scope"]) <= set(ds.SCOPES)
    assert got["total"] == sum(got["by_scope"].values()) + got["unscoped"]
    # lost.1, the prefetch's two halves, bc.0 and relayout.1 stand under a neighbour's
    # scope (what a fusion holds is its own: conv_fusion and chain.1 are not among them)
    assert got["inherited"] == 5


@pytest.mark.parametrize("opcode", sorted(ds.NOT_OPERATIONS))
def test_the_six_opcodes_that_are_no_operation_are_left_out(opcode):
    _entry, comps = ds._parse(HLO)
    seen = [i.name for body in comps.values() for i in body if i.opcode == opcode]
    assert seen, f"the text holds no {opcode}"
    assert not set(seen) & set(ds.table(HLO))


@pytest.mark.parametrize("op_name,path", [
    (f"{_MATCH}/jit(match_segment_block)/cko.seg.suffix/b0.st017/jit(_pad)/pad", "cko.seg.suffix/b0.st017"),
    (f"{_MATCH}/jit(match_segment_block)/cko.seg.suffix/b0.st001/reach/tbij,tbjn->tbin/dot_general",
     "cko.seg.suffix/b0.st001/reach"),  # a class gap as matmuls, priced apart from the structure's passes
    (f"{_MATCH}/cko.seg.suffix/b0.st001/jit(_pad)/reach", "cko.seg.suffix/b0.st001"),  # only right beneath
    (f"{_MATCH}/cko.transform/reach/and", "cko.transform/reach"),  # a transform's name is the level itself
    (f"{_MATCH}/cko.seg.suffix", "cko.seg.suffix"),  # nothing beneath it
    (f"{_MATCH}/cko.flat/jit(_scan_flat_pallas)/pallas_call", "cko.flat"),  # no level beneath: cut at the name
    (f"{_MATCH}/cko.seg.chunk/while/body/jit(match_segment_block)/cko.seg.conv/ge", "cko.seg.conv"),
    (f"{_MATCH}/cko.transform/lowercase/cko.bogus/and", "cko.transform/lowercase"),  # not in the registry
    (f"{_MATCH}/cko.bogus/and", "unscoped"),
    (f"{_MATCH}/jit(packbits)/reduce_sum", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_a_scope_path_is_cut_at_the_registrys_name(op_name, path):
    assert ds.scope_path(op_name) == path
    assert ds.scope_of(path) in ds.SCOPES or path == "unscoped"


def test_a_text_without_an_entry_computation_is_refused():
    with pytest.raises(ValueError):
        ds.count("HloModule x\n\n%f (a: s32[]) -> s32[] {\n  ROOT %a = s32[] parameter(0)\n}\n")


# -- a capture priced by scope, on events kept here (no JAX) -----------------------------

MS = 1_000_000  # the trace's clock is nanoseconds


def _events():
    """One device plane: two runs of the matcher, one of the post stage,
    one of an executable that is not ours. The matcher's ``while`` spans
    its body's two operations (self time: what they leave)."""
    match, post = "jit_cko_match_32x512(11)", "jit_cko_eval_post_32x512(12)"
    ops = []
    for t0 in (0, 20 * MS):
        ops += [
            # joined by the event's own stat
            ["%conv_fusion = pred[16,34,4]{2,1,0} fusion(...)", t0, 2 * MS,
             f"{_MATCH}/jit(match_segment_block)/cko.seg.conv/ge"],
            # joined by name through the table
            ["%chain.1 = pred[16,34,4]{2,1,0} fusion(...)", t0 + 2 * MS, 1 * MS, None],
            ["%while.1 = (s32[], pred[16,34,4]) while(...)", t0 + 3 * MS, 5 * MS, None],
            ["%inner.1 = pred[16,34,4] fusion(...)", t0 + 3 * MS + MS // 2, 2 * MS, None],
            ["%inner.1 = pred[16,34,4] fusion(...)", t0 + 6 * MS, 1 * MS, None],
            # in no table and without a stat
            ["%copy.999 = pred[16] copy(...)", t0 + 8 * MS, 1 * MS, None],
        ]
    ops.append(["%fusion.5 = s32[128,9] fusion(...)", 10 * MS, MS // 2,
                "jit(cko_eval_post_32x512)/jit(main)/cko.post.match/jit(post_match)/and"])
    ops.append(["%fusion.5 = f32[8] fusion(...)", 12 * MS, 3 * MS, None])  # not ours: left out
    return {"devices": [{
        "name": "/device:TPU:0",
        "modules": [[match, 0, 10 * MS], [post, 10 * MS, 1 * MS], ["jit_convert(3)", 12 * MS, 3 * MS],
                    [match, 20 * MS, 10 * MS]],
        "ops": ops,
    }]}


def test_reduce_by_scope_joins_by_stat_and_by_name_and_a_loop_keeps_what_its_body_leaves():
    got = ds.reduce_by_scope(_events(), {"cko_match_32x512": ds.table(HLO)})
    assert set(got) == {"cko_match_32x512", "cko_eval_post_32x512"}
    m = got["cko_match_32x512"]
    assert m["runs"] == 2 and m["module_s"] == pytest.approx(0.020)
    assert m["joined_by"] == {"stat": 2, "table": 8, "none": 2}
    per_run = {path: (cell["s"] / 2, cell["ops"] / 2) for path, cell in m["scopes"].items()}
    assert per_run == {
        "cko.seg.conv": (pytest.approx(0.002), 1),
        "cko.seg.suffix/b0.st017": (pytest.approx(0.001), 1),
        "cko.seg.chunk": (pytest.approx(0.002), 1),  # 5 ms less the 3 ms its body ran
        "cko.seg.final": (pytest.approx(0.003), 2),
        "unscoped": (pytest.approx(0.001), 1),
    }
    assert m["ops_s"] == pytest.approx(0.018)  # the rest of module_s is the device between operations
    assert m["dearest"][0] == ["inner.1", "cko.seg.final", pytest.approx(0.006)]
    p = got["cko_eval_post_32x512"]
    assert p["runs"] == 1 and p["scopes"] == {"cko.post.match": {"s": pytest.approx(0.0005), "ops": 1}}


def test_the_table_by_scope_names_scopes_structures_and_the_dearest_instructions():
    reduced = ds.reduce_by_scope(_events(), {"cko_match_32x512": ds.table(HLO)})
    assert ds.by_registry_scope(reduced["cko_match_32x512"]["scopes"])["cko.seg.suffix"] == {
        "s": pytest.approx(0.002), "ops": 2}
    assert ds.dearest_beneath(reduced["cko_match_32x512"]["scopes"], "cko.seg.suffix") == [
        ["cko.seg.suffix/b0.st017", pytest.approx(0.002), 2]]
    text = ds.format_table(reduced)
    for word in ("cko_match_32x512: 2 runs, 10.0000 ms a run", "cko.seg.final", "unscoped",
                 "cko.seg.suffix/b0.st017", "inner.1", "cko_eval_post_32x512: 1 runs"):
        assert word in text, word


def test_two_models_executables_of_one_name_share_what_agrees():
    merged = ds.merge_tables([
        {"name": "cko_match_32x64", "model": "aa", "table": {"fusion.1": "cko.flat", "copy.2": "cko.slab"}},
        {"name": "cko_match_32x64", "model": "bb", "table": {"fusion.1": "cko.seg.conv", "copy.2": "cko.slab"}},
        {"name": "cko_eval_post_32x64", "model": "aa", "table": None},
    ])
    assert merged == {"cko_match_32x64": {"fusion.1": "ambiguous", "copy.2": "cko.slab"},
                      "cko_eval_post_32x64": {}}


def test_names_of_executables_and_instructions_as_a_tpu_trace_prints_them():
    assert ds.executable_name("jit_cko_match_32x512(1234567890)") == "cko_match_32x512"
    assert ds.executable_name("cko_match_32x512") == "cko_match_32x512"
    assert ds.instruction_name("%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop") == "fusion.3"
    assert ds.instruction_name("cko_flat_bin0.1") == "cko_flat_bin0.1"


# -- the registry is closed --------------------------------------------------------------


def _named_scope_arguments():
    """(file, line, the argument's node) of every ``named_scope(...)`` call in the package."""
    for path in sorted(PACKAGE.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "named_scope"):
                yield path.relative_to(PACKAGE), node.lineno, node.args[0]


def test_every_named_scope_literal_in_the_package_is_in_the_registry():
    calls = list(_named_scope_arguments())
    literals = [(f, line, a.value) for f, line, a in calls if isinstance(a, ast.Constant)]
    assert {name for _f, _l, name in literals} == set(ds.SCOPES), \
        "a scope of the registry is entered nowhere, or one outside it is"
    strangers = [(str(f), line, name) for f, line, name in literals if name not in ds.SCOPES]
    assert not strangers
    # What is no literal is the level beneath a scope that carries one: the
    # suffix structure's index, the pipeline's transforms; and the one name
    # kept beneath a structure (``ops/segment.py:_REACH_SCOPE``).
    computed = [(str(f), line) for f, line, a in calls if not isinstance(a, ast.Constant)]
    assert len(computed) == len(ds.SUBSCOPED) + 1, computed
    from coraza_kubernetes_operator_tpu.ops import segment

    assert segment._REACH_SCOPE == ds.REACH
    assert all(name.startswith("cko.") for name in ds.SCOPES)
    assert ds.SUBSCOPED <= set(ds.SCOPES)


# -- the program, compiled on the CPU ----------------------------------------------------

SAMPLE = (Path(__file__).resolve().parents[1] / "wafbench/configs/operator-sample/rules.conf")

# Gaps, anchors, a class gap, a literal past MAX_SEG_LEN (24) that the planner
# splits, a solo literal and a dense DFA: bucket, suffix, final, fold and a
# split all occur, and a device transform pipeline.
SEGMENT_HEAVY = r"""
SecRuleEngine On
SecRequestBodyAccess On
SecDefaultAction "phase:2,log,pass"
SecRule ARGS "@rx (?i)union\s+select" "id:1,phase:2,deny,status:403"
SecRule ARGS "@rx ^admin[a-z]{2,5}root$" "id:2,phase:2,deny,status:403"
SecRule ARGS "@rx etc/passwd.{0,20}shadow" "id:3,phase:2,deny,status:403,t:lowercase"
SecRule ARGS "@rx etc/group.{0,20}shadow" "id:4,phase:2,deny,status:403,t:lowercase"
SecRule ARGS "@rx <script[^>]*>alert" "id:5,phase:2,deny,status:403,t:lowercase,t:urlDecodeUni"
SecRule REQUEST_URI "@contains /this-is-a-literal-longer-than-24-bytes/x" "id:6,phase:2,deny,status:403"
SecRule ARGS "@contains evilmonkey" "id:7,phase:2,deny,status:403"
SecRule ARGS "@rx (e|fg)+h" "id:8,phase:2,deny,status:403"
"""

RULE_SETS = {
    "operator-sample": (lambda: SAMPLE.read_text(),  # two @rx rules, both on the conv tier
                        {"cko.slab", "cko.transform", "cko.seg.embed", "cko.seg.nce", "cko.seg.conv",
                         "cko.seg.suffix", "cko.seg.final", "cko.stitch", "cko.post.match", "cko.post.pack"}),
    "segment-heavy": (lambda: SEGMENT_HEAVY,
                      {"cko.slab", "cko.transform", "cko.seg.embed", "cko.seg.nce", "cko.seg.conv",
                       "cko.seg.bucket", "cko.seg.suffix", "cko.seg.final", "cko.seg.fold", "cko.flat",
                       "cko.stitch", "cko.post.match", "cko.post.pack"}),
}


def _canary_executables(rules: str) -> tuple[dict, dict]:
    """Compile the promotion canary's matcher and post stage for ``rules``
    through the one compile site; ({name: text}, the engine's automata
    summary). Every cache between the source and the executable is
    emptied first, so that what is compiled is traced now."""
    import jax

    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE
    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine
    from coraza_kubernetes_operator_tpu.models import waf_model

    EXEC_CACHE.clear()
    waf_model._stage_executables.clear()
    jax.clear_caches()
    engine = WafEngine(rules)
    engine.prewarm()
    with EXEC_CACHE._lock:
        texts = {key[0]: compiled.as_text() for key, compiled in EXEC_CACHE._entries.items()}
    return texts, engine.automata_summary()


def _opcode_histogram(text: str) -> collections.Counter:
    _entry, comps = ds._parse(text)
    return collections.Counter(i.opcode for body in comps.values() for i in body)


@pytest.fixture(scope="module", params=sorted(RULE_SETS))
def canary(request):
    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE

    rules, scopes = RULE_SETS[request.param]
    texts, summary = _canary_executables(rules())
    stats = EXEC_CACHE.stats()
    tables = EXEC_CACHE.scope_tables()
    yield {"name": request.param, "texts": texts, "summary": summary, "scopes": scopes,
           "stats": stats, "tables": tables, "rules": rules()}
    EXEC_CACHE.clear()


def test_every_scope_the_model_exercises_is_present(canary):
    if canary["name"] == "segment-heavy":
        assert canary["summary"]["segment_splits"] >= 1 and canary["summary"]["flat_bins"] >= 1
    counted = {e["name"]: e["device_ops"] for e in canary["stats"]["executables"]}
    assert sorted(counted) == sorted(canary["texts"]) and len(counted) == 2
    present = set().union(*(ops["by_scope"] for ops in counted.values()))
    assert canary["scopes"] <= present, canary["scopes"] - present
    match = next(ops for name, ops in counted.items() if name.startswith("cko_match_"))
    post = next(ops for name, ops in counted.items() if name.startswith("cko_eval_post_"))
    assert not any(s.startswith("cko.post.") for s in match["by_scope"])
    assert all(s.startswith("cko.post.") for s in post["by_scope"])


def test_next_to_nothing_stands_outside_every_scope(canary):
    for entry in canary["stats"]["executables"]:
        ops = entry["device_ops"]
        assert ops["total"] == sum(ops["by_scope"].values()) + ops["unscoped"]
        assert ops["unscoped"] <= 0.05 * ops["total"], (entry["name"], ops)


def test_the_counts_kept_at_the_compile_are_those_of_the_resident_text(canary):
    assert canary["stats"]["scope_table_errors"] == 0
    for entry in canary["tables"]:
        text = canary["texts"][entry["name"]]
        assert entry["table"] == ds.table(text)
        kept = next(e for e in canary["stats"]["executables"] if e["name"] == entry["name"])
        assert kept["device_ops"] == ds.count(text) and kept["model"] == entry["model"]


def test_two_sites_of_one_shift_helper_keep_their_own_scope(canary):
    """``_lshift3`` / ``_rshift3`` go through ``jnp.pad``, a jitted
    function: were it lowered once and called from both sites, the pads
    of the finals would read the suffix's scope."""
    text = next(t for name, t in canary["texts"].items() if name.startswith("cko_match_"))
    pads = collections.Counter(
        ds.scope_of(ds.scope_path(name)) for name in ds._OP_NAME.findall(text)
        if name.endswith("/pad") or "/jit(_pad)/" in name)
    assert pads["cko.seg.suffix"] and pads["cko.seg.final"], pads


def test_the_prefix_counts_dot_stands_under_its_own_scope(canary):
    """Both texts hold a class gap (``<script[^>]*>``): the matmul that
    counts the bytes outside the class is how ``cko.seg.nce`` is seen to
    engage, so it is priced there and nowhere else."""
    name, text = next((n, t) for n, t in canary["texts"].items() if n.startswith("cko_match_"))
    _entry, comps = ds._parse(text)
    table = ds.table(text)
    held_by = {i.name: op.name for body in comps.values() for op in body if op.fused
               for i in comps.get(op.fused, ())}
    dots = [i for body in comps.values() for i in body
            if i.opcode == "dot" and i.op_name and "/cko.seg.nce/" in i.op_name]
    assert dots, "no prefix-count matmul in the optimized HLO"
    for dot in dots:
        assert ds.scope_path(dot.op_name) == "cko.seg.nce"
        assert table[held_by.get(dot.name, dot.name)] == "cko.seg.nce"
    elsewhere = [i.name for body in comps.values() for i in body
                 if i.opcode == "dot" and ds.scope_path(i.op_name) == "cko.seg.embed"]
    assert not elsewhere, elsewhere
    kept = next(e for e in canary["stats"]["executables"] if e["name"] == name)
    assert kept["device_ops"]["by_scope"]["cko.seg.nce"] >= len({held_by.get(d.name, d.name) for d in dots})


def test_names_change_metadata_and_nothing_else(canary, monkeypatch):
    import jax

    from jax._src import compilation_cache as cc

    # The persistent cache's key leaves metadata out: it would hand the
    # named executable back for the nameless program.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    try:
        bare, _summary = _canary_executables(canary["rules"])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert sorted(bare) == sorted(canary["texts"])
    for name, text in canary["texts"].items():
        assert ds.count(bare[name])["by_scope"] == {}, "the patch did not reach the trace"
        assert _opcode_histogram(bare[name]) == _opcode_histogram(text), name


# -- the counters of the executable cache ------------------------------------------------


class _NoText:
    def __init__(self, compiled):
        self._compiled = compiled

    def as_text(self):
        raise RuntimeError("this executable keeps no text")

    def __call__(self, *args):
        return self._compiled(*args)


class _Lowered:
    def __init__(self, lowered, wrap):
        self._lowered, self._wrap = lowered, wrap

    def compile(self):
        return self._wrap(self._lowered.compile())


def _jitted(name: str, wrap=lambda c: c):
    """A jitted function under ``name`` whose executables come back
    through ``wrap`` (the cache calls ``.lower(...).compile()``)."""
    import jax

    def fn(model, x):
        with jax.named_scope("cko.stitch"):
            return x + model

    fn.__name__ = fn.__qualname__ = name
    inner = jax.jit(fn)

    class Jitted:
        __name__ = name

        def lower(self, *args, **kwargs):
            return _Lowered(inner.lower(*args, **kwargs), wrap)

    return Jitted()


def test_stats_hold_an_entry_per_resident_cko_executable_and_clear_empties_them():
    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.compile_cache import ExecutableCache

    cache = ExecutableCache()
    x = np.zeros((4,), np.int32)
    for model in (np.int32(1), np.float32(1)):  # two models, one name: two entries
        assert cache.warm(_jitted("cko_match_4x1"), (model, x), {})
    assert cache.warm(_jitted("plain_helper"), (np.int32(1), x), {})  # not ours: not counted
    stats = cache.stats()
    assert stats["entries"] == 3 and stats["scope_table_errors"] == 0
    assert [e["name"] for e in stats["executables"]] == ["cko_match_4x1"] * 2
    assert len({e["model"] for e in stats["executables"]}) == 2
    for e in stats["executables"]:
        ops = e["device_ops"]
        assert ops["total"] >= 1 and ops["by_scope"] == {"cko.stitch": ops["total"]}
    json.dumps(stats)  # what /waf/v1/stats serves
    assert [t["name"] for t in cache.scope_tables()] == ["cko_match_4x1"] * 2
    cache.clear()
    assert cache.stats()["executables"] == [] and cache.scope_tables() == []


def test_a_text_that_raises_is_a_boundary_and_never_an_exception_into_a_compile():
    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.compile_cache import ExecutableCache

    cache = ExecutableCache()
    x = np.zeros((4,), np.int32)
    assert cache.warm(_jitted("cko_match_4x1", _NoText), (np.int32(1), x), {})
    stats = cache.stats()
    assert stats["scope_table_errors"] == 1 and stats["misses"] == 1
    assert stats["executables"] == [
        {"name": "cko_match_4x1", "model": stats["executables"][0]["model"], "device_ops": None,
         "seg_plan": None}]  # no conv tier was traced under it
    assert cache.scope_tables()[0]["table"] is None
    # a text the walker cannot read counts the same
    assert cache.warm(_jitted("cko_match_8x1", lambda c: type(
        "Odd", (), {"as_text": lambda self: "not HLO", "__call__": lambda self, *a: c(*a)})()),
        (np.int32(1), x), {})
    assert cache.stats()["scope_table_errors"] == 2


def test_the_persistent_caches_keys_carry_the_registrys_salt():
    """JAX leaves metadata out of the persistent cache's key: without the
    salt a build with other scopes would be served this build's names."""
    from jax._src import cache_key

    from coraza_kubernetes_operator_tpu.engine.compile_cache import configure_persistent_cache

    configure_persistent_cache()  # wherever the cache is wired, the salt is
    assert cache_key.custom_hook() == ds.CACHE_KEY_SALT
    assert all(name in ds.CACHE_KEY_SALT for name in ds.SCOPES)


# -- the operator's reading: a dump through /waf/v1/profile, the CLI ----------------------


def test_profile_stop_writes_the_tables_of_every_resident_executable_beside_the_dump(tmp_path):
    from coraza_kubernetes_operator_tpu.engine import HttpRequest, WafEngine
    from coraza_kubernetes_operator_tpu.engine.compile_cache import EXEC_CACHE
    from coraza_kubernetes_operator_tpu.sidecar import SidecarConfig, TpuEngineSidecar

    EXEC_CACHE.clear()
    engine = WafEngine(SEGMENT_HEAVY)
    sc = TpuEngineSidecar(
        SidecarConfig(host="127.0.0.1", port=0, metrics_auth_token="tok"), engine=engine)
    try:
        status, body, _ = sc.profile_reply(
            "Bearer tok", json.dumps({"action": "start", "dir": str(tmp_path)}).encode())
        assert status == 200, body
        assert engine.evaluate_one(HttpRequest(uri="/?q=evilmonkey")).interrupted
        status, body, _ = sc.profile_reply("Bearer tok", b'{"action": "stop"}')
        assert status == 200, body
    finally:
        EXEC_CACHE.clear()
    scopes_file = tmp_path / "device_scopes.json"
    assert json.loads(body)["device_scopes"] == str(scopes_file)
    listed = json.loads(scopes_file.read_text())["executables"]
    assert sorted(e["name"].split("_")[1] for e in listed) == ["eval", "match"]
    for entry in listed:
        assert set(entry) == {"name", "model", "table"} and entry["table"]
        assert {ds.scope_of(path) for path in entry["table"].values()} <= set(ds.SCOPES)
    assert list(tmp_path.glob("plugins/profile/*/*.xplane.pb")), "the dump itself"


def test_the_cli_prints_the_table_by_scope_of_a_dump(tmp_path, monkeypatch, capsys):
    (tmp_path / "device_scopes.json").write_text(json.dumps({"executables": [
        {"name": "cko_match_32x512", "model": "aa", "table": ds.table(HLO)}]}))
    monkeypatch.setattr(ds, "extract", lambda path: _events())  # the CPU has no device plane
    assert ds.main([str(tmp_path)]) == 0
    printed = capsys.readouterr().out
    assert printed == ds.format_table(
        ds.reduce_by_scope(_events(), {"cko_match_32x512": ds.table(HLO)})) + "\n"
    assert "cko.seg.final" in printed and "table 8" in printed
    # without the tables the events' own stat is all there is to join by
    (tmp_path / "device_scopes.json").unlink()
    assert ds.main([str(tmp_path)]) == 0
    out = capsys.readouterr()
    assert "table 0" in out.out and "joining by the events' own stat alone" in out.err
    assert ds.main([]) == 2
