"""Device scopes: the ``cko.`` namespace inside the traced program.

The host side names what it does per window (``observability/stages.py``:
``cko.<stage>`` spans on the profiler's clock); the executables are named
by role and shape (``jit_cko_match_32x512``) and the Pallas kernels by
family (``cko_flat_bin<i>``). One level down, inside a matcher
executable, a device trace has XLA's running counter (``fusion.10530``)
and nothing else. This module is that level:

**names** — ``SCOPES`` is the closed registry of ``jax.named_scope``
names entered where the matcher and the post stage are traced
(``models/waf_model.py``, ``ops/segment.py``). A scope is metadata
(``op_name``): it changes no HLO instruction. Two scopes carry one more
level (``SUBSCOPED``): ``cko.seg.suffix/b<block>.st<structure>`` and
``cko.transform/<transforms joined by +>``; beneath a structure, the
operations of a class gap run as a reachability matmul stand under
``.../reach`` (``REACH``), priced apart from the structure's passes
and counted with them. Scopes nest (the row-chunked
conv tier runs whole segment blocks inside ``cko.seg.chunk``'s
``lax.map``): an operation stands under the INNERMOST registered scope
of its ``op_name``.

**counters** — ``count(hlo_text)`` walks a compiled executable's
optimized HLO once and says how many device operations a launch is and
under which scope each stands (``ExecutableCache._compile`` keeps the
result per ``cko_*`` executable; ``/waf/v1/stats``
``compile_cache.executables``).

**prices** — ``table(hlo_text)`` maps instruction name to scope path,
and ``reduce_by_scope(events, tables)`` turns a profiler capture's
``XLA Ops`` events into device seconds per executable and scope::

    python -m coraza_kubernetes_operator_tpu.observability.device_scopes <dump dir>

reads a dump taken through ``POST /waf/v1/profile`` (the stop writes
``device_scopes.json``, the tables of every resident ``cko_*``
executable, beside it) and prints the table by scope.

Nothing here imports JAX but ``extract`` (the profiler's reader), and
nothing runs per window, per request or per stats call.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path
from typing import NamedTuple

# name -> what runs under it. Closed: tests/test_device_scopes.py holds
# every ``named_scope`` literal in the package to this table.
SCOPES = {
    "cko.slab": "match_tier_packed: the static slices and bitcasts of the tier's one operand",
    "cko.transform": "match_tier: a device transform pipeline (beneath: its transforms joined by +)",
    "cko.seg.embed": "match_segment_block: dpad, channel planes, the bf16 stack; position iotas, gap-class membership planes",
    "cko.seg.nce": "match_segment_block: the gap classes' exclusive prefix counts (NCE), blocked triangular matmuls; the reachability tables made of them for a wide structure's unbounded gaps",
    "cko.seg.conv": "match_segment_block: conv_general_dilated and the compare that gives m_all",
    "cko.seg.bucket": "match_segment_block tier (b): signature-bucketed chains and their lax.cond gate",
    "cko.seg.suffix": "match_segment_block tier (a): right-to-left passes of a suffix structure (beneath: b<n>.st<i>, and reach beneath that: a class gap as matmuls)",
    "cko.seg.final": "match_segment_block: gates g3 / gj3, AND-any reductions and their lax.cond",
    "cko.seg.fold": "match_segment_block: concatenation of columns, the b2g matmul, always",
    "cko.seg.chunk": "segment_tier_hits, row-chunked: pad / stack / reshape into chunks, the lax.map, reassembly",
    "cko.seg.tile": "segment_tier_hits, column-tiled: the barrier that runs a chunk's column tiles one after another, their concatenation",
    "cko.seg.long": "segment_tier_hits, long-bank fallback: scan_dfa_bank over the long banks, the seg_perm matmul",
    "cko.flat": "scan_flat_bank: class maps, slot layout, the Pallas call cko_flat_bin<i>, unpacking columns",
    "cko.dense": "match_tier: scan_dfa_bank, the XLA scan of a dense-DFA block no bin covers ",
    "cko.stitch": "match_tier, match_tier_packed: concatenation of the blocks' columns, packbits",
    "cko.post.unpack": "eval_post_tiered: slab views, hit rows unpacked and taken by uid",
    "cko.post.match": "eval_post_tiered: post_match",
    "cko.post.pack": "eval_post_tiered: _pack_verdicts",
}
SUBSCOPED = frozenset({"cko.seg.suffix", "cko.transform"})
# The one third level kept: beneath a suffix structure (ops/segment.py:_REACH_SCOPE).
REACH = "reach"
UNSCOPED = "unscoped"
EXECUTABLE_PREFIX = "cko_"  # stage_executable's names: cko_<role>_<shape>

# Names are metadata, and JAX leaves metadata out of its persistent
# compilation cache's key: an executable cached by a build with other
# scopes would come back carrying that build's names. This string goes
# into the key (engine/compile_cache.py); bump the number when a scope
# moves without the registry changing.
CACHE_KEY_SALT = "cko-scopes-1:" + ",".join(sorted(SCOPES))

# Not device operations: no kernel, no copy, nothing a trace shows.
NOT_OPERATIONS = frozenset(
    {"parameter", "constant", "tuple", "get-tuple-element", "bitcast", "after-all"}
)


def scope_path(op_name: str | None) -> str:
    """``jit(cko_match_32x512)/.../cko.seg.suffix/b0.st017/and`` ->
    ``cko.seg.suffix/b0.st017``: the innermost registered component,
    with the component beneath it where the scope carries one."""
    if not op_name or "cko." not in op_name:
        return UNSCOPED
    parts = op_name.split("/")
    for i in range(len(parts) - 1, -1, -1):
        name = parts[i]
        if name in SCOPES:
            if name in SUBSCOPED and i + 1 < len(parts):
                deeper = f"/{REACH}" if parts[i + 2 : i + 3] == [REACH] else ""
                return f"{name}/{parts[i + 1]}{deeper}"
            return name
    return UNSCOPED


def scope_of(path: str) -> str:
    """The registry's name of a scope path."""
    return path.split("/", 1)[0]


# -- the walk over optimized HLO text ----------------------------------------------

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_PLAIN_TYPE_OPCODE = re.compile(r"[^(\s]\S*\s+([\w\-]+)\(")  # a type without blanks, then the opcode
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_CALLED = re.compile(r"\bto_apply=%?([\w.\-]+)")
_WHILE = re.compile(r"\b(?:condition|body)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TRUE_FALSE = re.compile(r"\b(?:true|false)_computation=%?([\w.\-]+)")
_OPEN, _CLOSE = "([{", ")]}"
# Made by the compiler to carry a value to where it is used (a relayout, a
# prefetch into fast memory): priced to what they were made for.
_MOVES = frozenset({"copy", "copy-start", "copy-done", "slice-start", "slice-done"})
# A value's other names: they pass a user's scope on to what feeds them.
_VIEWS = frozenset({"tuple", "get-tuple-element", "bitcast"})


class _Instr(NamedTuple):
    name: str
    opcode: str
    op_name: str | None
    operands: tuple  # instruction names, as the text gives them (``%name``)
    runs: tuple  # computations it runs: a loop's condition and body, branches, a call
    fused: str | None  # a fusion's computation: one operation, never walked
    root: bool


def _opcode_at(rest: str) -> re.Match | None:
    """The opcode of ``<type> <opcode>(<operands>)...``. A tuple type
    holds blanks, a TPU layout parentheses: the opcode follows the first
    blank outside every bracket."""
    if not rest.startswith("("):
        return _PLAIN_TYPE_OPCODE.match(rest)
    depth = 0
    for i, ch in enumerate(rest):
        if ch in _OPEN:
            depth += 1
        elif ch in _CLOSE:
            depth -= 1
        elif ch == " " and depth == 0:
            return _OPCODE.match(rest, i)
    return None


def _parse(hlo_text: str) -> tuple[str, dict[str, list[_Instr]]]:
    """(entry computation, {computation: its instructions in order})."""
    comps: dict[str, list[_Instr]] = {}
    entry = None
    body = None
    for line in hlo_text.splitlines():
        if body is None:
            m = _COMPUTATION.match(line)
            if m:
                body = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            body = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)
        at = _opcode_at(rest)
        if at is None:
            continue
        opcode = at.group(1)
        # Operands print as bare names, so their list ends at the first ")".
        operands = tuple(_OPERAND.findall(rest, at.end(), max(rest.find(")", at.end()), at.end())))
        runs, fused = (), None
        if opcode == "fusion":
            called = _FUSED.search(rest)
            fused = called.group(1) if called else None
        elif opcode == "call":
            called = _CALLED.search(rest)
            runs = (called.group(1),) if called else ()
        elif opcode == "while":
            runs = tuple(_WHILE.findall(rest))
        elif opcode == "conditional":
            listed = _BRANCHES.search(rest)
            if listed:
                runs = tuple(c.strip().lstrip("%") for c in listed.group(1).split(",") if c.strip())
            else:
                runs = tuple(_TRUE_FALSE.findall(rest))
        named = _OP_NAME.search(rest)
        body.append(_Instr(m.group(2), opcode, named.group(1) if named else None, operands, runs,
                           fused, bool(m.group(1))))
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    return entry, comps


def _fused_scope(body: list[_Instr]) -> str:
    """A fusion that carries no scope of its own has its root's; where a
    compiler pass made the root anew and left it bare (a rewritten
    ``dot``, a ``copy``), the scope most of its instructions carry."""
    root = next((scope_path(i.op_name) for i in body if i.root), UNSCOPED)
    if root != UNSCOPED:
        return root
    votes: dict[str, int] = {}
    for instr in body:
        path = scope_path(instr.op_name)
        if path != UNSCOPED:
            votes[path] = votes.get(path, 0) + 1
    return max(votes, key=votes.get) if votes else UNSCOPED


def _resolve(body: list[_Instr], comps: dict, outer: str) -> tuple[dict[str, str], set[str]]:
    """({instruction: scope path}, the instructions that carry none of
    their own) for one computation run from an instruction under
    ``outer``. What the compiler made carries no name (on a v5e two
    fifths of crs-lite's matcher: relayout copies, prefetches, the
    pieces of a rewritten reduction, a branch's operands), but it
    was made for something that does. In this order: a value the compiler
    moves stands under its first user's scope (what it was moved for);
    anything else under its first operand's (what it was made from), else
    its first user's, else its loop's or conditional's. A parameter or a
    constant passes nothing on: everything in sight may use it."""
    local: dict[str, str] = {}
    for i in body:
        path = scope_path(i.op_name)
        if path == UNSCOPED and i.fused:
            path = _fused_scope(comps.get(i.fused, ()))
        local[i.name] = path
    bare = [i for i in body if local[i.name] == UNSCOPED
            and (i.opcode in _VIEWS or i.opcode not in NOT_OPERATIONS)]
    users: dict[str, list[str]] = {}
    for i in body:
        for name in i.operands:
            users.setdefault(name, []).append(i.name)

    def first(names) -> str:
        return next((local[n] for n in names if local.get(n, UNSCOPED) != UNSCOPED), UNSCOPED)

    for i in reversed(bare):
        if i.opcode in _MOVES or i.opcode in _VIEWS:
            local[i.name] = first(users.get(i.name, ()))
    for i in bare:
        if local[i.name] == UNSCOPED:
            local[i.name] = first(i.operands)
    for i in reversed(bare):
        if local[i.name] == UNSCOPED:
            local[i.name] = first(users.get(i.name, ()))
    for i in bare:
        if local[i.name] == UNSCOPED:
            local[i.name] = outer
    return local, {i.name for i in bare}


def walk(hlo_text: str) -> tuple[dict[str, str], int]:
    """({operation: scope path}, how many of them stand under a scope
    that is not in their own name): ``table`` and ``count`` in one pass
    over the text."""
    entry, comps = _parse(hlo_text)
    out: dict[str, str] = {}
    inherited = 0
    seen, todo = {entry}, [(entry, UNSCOPED)]
    while todo:
        comp, outer = todo.pop()
        body = comps.get(comp, ())
        local, bare = _resolve(body, comps, outer)
        for i in body:
            for run in i.runs:
                if run not in seen:
                    seen.add(run)
                    todo.append((run, local[i.name]))
            if i.opcode not in NOT_OPERATIONS:
                out[i.name] = local[i.name]
                inherited += i.name in bare and local[i.name] != UNSCOPED
    return out, inherited


def table(hlo_text: str) -> dict[str, str]:
    """Instruction name -> scope path, for every device operation of the
    executable: an instruction of the entry computation or of a
    computation reached from it through ``while`` (body and condition),
    ``conditional`` (every branch) or ``call``, each once. A fusion is
    one operation (``_fused_scope``); reducers and comparators
    (``to_apply``) are none; neither are ``NOT_OPERATIONS``. An operation
    without a scope in its own name inherits one (``_resolve``)."""
    return walk(hlo_text)[0]


def count(hlo_text: str) -> dict:
    """What a launch is made of: ``{"total", "by_scope": {scope: n},
    "unscoped", "inherited"}``, static (a loop body counts once, whatever
    its trips). ``inherited`` of ``total`` stand under their scope by
    what they feed or are fed by, not by their own name."""
    return counts(*walk(hlo_text))


def counts(paths: dict[str, str], inherited: int) -> dict:
    """``count``'s dictionary from what ``walk`` returned."""
    by_scope: dict[str, int] = {}
    for path in paths.values():
        scope = scope_of(path)
        by_scope[scope] = by_scope.get(scope, 0) + 1
    unscoped = by_scope.pop(UNSCOPED, 0)
    return {"total": len(paths), "by_scope": dict(sorted(by_scope.items())),
            "unscoped": unscoped, "inherited": inherited}


# -- a capture, priced by scope ----------------------------------------------------------


def instruction_name(text: str) -> str:
    """The TPU trace names an operation by its whole HLO text
    (``%fusion.3 = f32[...] fusion(...)``): the name is what stands
    before `` = ``."""
    return text.split(" = ", 1)[0].lstrip("%")


def executable_name(module_event: str) -> str:
    """``jit_cko_match_32x512(1234567890)`` -> ``cko_match_32x512``."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def extract(path: Path) -> dict:
    """A capture's device planes as plain lists (nanoseconds):
    ``{"devices": [{"name", "modules": [[name, start, dur]], "ops":
    [[name, start, dur, op_name stat or None]]}], "op_stats": [keys]}``.
    The one function here that imports JAX (the profiler's reader; run it
    with ``JAX_PLATFORMS=cpu`` beside a process that holds the chip)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(Path(path))))
    devices, stat_keys = [], set()
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = {"name": plane.name, "modules": [], "ops": []}
        for line in plane.lines:
            if line.name == "XLA Modules":
                dev["modules"] = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            elif line.name == "XLA Ops":
                for e in line.events:
                    stats = dict(e.stats)
                    stat_keys.update(stats)
                    named = stats.get("tf_op") or stats.get("op_name")
                    dev["ops"].append([e.name, e.start_ns, e.duration_ns,
                                       named if isinstance(named, str) else None])
        devices.append(dev)
    return {"devices": devices, "op_stats": sorted(stat_keys)}


def _self_ns(events: list) -> list[float]:
    """Per event (in the order given, which is by start, the longer
    first), its duration less what its direct children cover: a ``while``
    spans its body's operations on the same line."""
    out = [float(e[2]) for e in events]
    stack: list[tuple[int, float]] = []  # (index, end)
    for i, e in enumerate(events):
        start, dur = e[1], e[2]
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= dur
        stack.append((i, start + dur))
    return [max(x, 0.0) for x in out]


def reduce_by_scope(events: dict, tables: dict[str, dict[str, str]]) -> dict:
    """Device seconds by executable and scope. ``events`` is what
    ``extract`` gives; ``tables`` maps an executable's name
    (``cko_match_32x512``) to its ``table``. Per ``cko_*`` executable
    that ran: ``runs``, ``module_s`` (its ``XLA Modules`` time),
    ``ops_s`` (the self time of its ``XLA Ops`` events: the rest of
    ``module_s`` is the device between operations), ``joined_by``
    (events joined by their own stat / through the table / not at all)
    and ``scopes``: path -> ``{"s", "ops"}`` with ``unscoped`` among
    them, plus ``dearest``: the ten instructions with most seconds."""
    out: dict[str, dict] = {}
    for dev in events["devices"]:
        modules = sorted((m for m in dev["modules"]
                          if executable_name(m[0]).startswith(EXECUTABLE_PREFIX)),
                         key=lambda m: m[1])
        ops = sorted(dev["ops"], key=lambda e: (e[1], -e[2]))
        selfs = _self_ns(ops)
        k = 0
        for m_name, m_start, m_dur in modules:
            exe = executable_name(m_name)
            acc = out.setdefault(exe, {"runs": 0, "module_s": 0.0, "ops_s": 0.0,
                                       "joined_by": {"stat": 0, "table": 0, "none": 0},
                                       "scopes": {}, "_instr": {}})
            acc["runs"] += 1
            acc["module_s"] += m_dur / 1e9
            names = tables.get(exe, {})
            while k < len(ops) and ops[k][1] < m_start:
                k += 1
            while k < len(ops) and ops[k][1] < m_start + m_dur:
                text, _s, _d, stat = ops[k]
                instr = instruction_name(text)
                path = scope_path(stat)
                if path != UNSCOPED:
                    acc["joined_by"]["stat"] += 1
                elif instr in names:
                    path = names[instr]
                    acc["joined_by"]["table"] += 1
                else:
                    acc["joined_by"]["none"] += 1
                sec = selfs[k] / 1e9
                cell = acc["scopes"].setdefault(path, {"s": 0.0, "ops": 0})
                cell["s"] += sec
                cell["ops"] += 1
                acc["ops_s"] += sec
                seen = acc["_instr"].setdefault(instr, [path, 0.0])
                seen[1] += sec
                k += 1
    for acc in out.values():
        instr = acc.pop("_instr")
        acc["dearest"] = [[name, path, sec] for name, (path, sec) in
                          sorted(instr.items(), key=lambda kv: -kv[1][1])[:10]]
    return out


def by_registry_scope(scopes: dict) -> dict:
    """A reduction's ``scopes`` (by path) summed to the registry's names."""
    out: dict[str, dict] = {}
    for path, cell in scopes.items():
        acc = out.setdefault(scope_of(path), {"s": 0.0, "ops": 0})
        acc["s"] += cell["s"]
        acc["ops"] += cell["ops"]
    return out


def dearest_beneath(scopes: dict, scope: str, n: int = 10) -> list:
    """The ``n`` paths beneath ``scope`` with most seconds: [path, s, ops]."""
    under = [(p, c["s"], c["ops"]) for p, c in scopes.items() if p.startswith(scope + "/")]
    return [list(x) for x in sorted(under, key=lambda x: -x[1])[:n]]


def merge_tables(executables: list[dict]) -> dict[str, dict[str, str]]:
    """``device_scopes.json``'s entries by executable name. Two models'
    executables of one name (two tenants' ``cko_match_32x64``) share a
    table where their instructions agree; one that differs reads
    ``ambiguous`` (a capture's event names its executable, not its model)."""
    out: dict[str, dict[str, str]] = {}
    for entry in executables:
        into = out.setdefault(entry["name"], {})
        for instr, path in (entry.get("table") or {}).items():
            if into.setdefault(instr, path) != path:
                into[instr] = "ambiguous"
    return out


def format_table(reduced: dict) -> str:
    """The table by scope, one block an executable: per scope its
    milliseconds a run, its share of the executable's ``XLA Modules``
    time (the scopes, ``unscoped`` and what lies between two operations
    add up to it), operations run, microseconds an operation; then the
    dearest paths beneath the scopes that carry a level, and the ten
    dearest instructions."""
    lines = []
    for exe, acc in sorted(reduced.items()):
        runs = max(acc["runs"], 1)
        lines.append(f"{exe}: {acc['runs']} runs, {1e3 * acc['module_s'] / runs:.4f} ms a run,"
                     f" operations joined by stat {acc['joined_by']['stat']} /"
                     f" table {acc['joined_by']['table']} / none {acc['joined_by']['none']}")
        lines.append(f"  {'scope':<18}{'ms a run':>12}{'share':>9}{'ops a run':>12}{'us an op':>11}")
        rows = sorted(by_registry_scope(acc["scopes"]).items(), key=lambda kv: -kv[1]["s"])
        whole = acc["module_s"] or 1.0
        for scope, cell in rows:
            per_op = 1e6 * cell["s"] / cell["ops"] if cell["ops"] else 0.0
            lines.append(f"  {scope:<18}{1e3 * cell['s'] / runs:>12.4f}{100 * cell['s'] / whole:>8.1f}%"
                         f"{cell['ops'] / runs:>12.1f}{per_op:>11.2f}")
        between = acc["module_s"] - acc["ops_s"]  # the device between two operations of a run
        lines.append(f"  {'(between ops)':<18}{1e3 * between / runs:>12.4f}{100 * between / whole:>8.1f}%")
        for scope in sorted(SUBSCOPED):
            for path, sec, ops in dearest_beneath(acc["scopes"], scope):
                lines.append(f"    {path:<40}{1e3 * sec / runs:>10.4f} ms{ops / runs:>10.1f} ops")
        for name, path, sec in acc["dearest"]:
            lines.append(f"    {name:<40}{1e3 * sec / runs:>10.4f} ms  {path}")
    return "\n".join(lines)


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print("usage: python -m coraza_kubernetes_operator_tpu.observability.device_scopes <dump dir>",
              file=sys.stderr)
        return 2
    dump = Path(argv[0])
    tables_file = dump / "device_scopes.json"
    tables = {}
    if tables_file.exists():
        tables = merge_tables(json.loads(tables_file.read_text())["executables"])
    else:
        print(f"no {tables_file}: joining by the events' own stat alone", file=sys.stderr)
    reduced = reduce_by_scope(extract(dump), tables)
    if not reduced:
        print("no cko_* executable ran on a device plane of this capture", file=sys.stderr)
        return 1
    print(format_table(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
