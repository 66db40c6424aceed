"""Sharded WAF evaluation: shard_map over a ('data', 'rule') mesh.

Layout: every DFA bank bucket is split evenly across the rule axis and its
per-shard tables stacked on a leading shard dimension, so bank leaves are
uniform arrays shardable with ``PartitionSpec('rule')``. Inside the
``shard_map`` body each device scans only its bank slice, all-gathers the
per-target hit bits over the rule axis (the only collective — G bits per
target, riding ICI), rebuilds the global group-hit matrix and runs the
shared post-match stages. Targets/requests are stacked on a leading data
axis with ``PartitionSpec('data')``.

A correctness contract, not a served path: no sidecar and no benchmark
cell runs this module (the served engine is one chip, its dense blocks in
the fused flat bins of ``ops/dfa_flat.py``), and tier-1 holds its verdicts
to the single-chip engine's on virtual CPU devices. Its banks take
``ops/dfa.py:scan_dfa_bank`` on every backend: on a TPU too that is the
XLA take-scan (the gather scan past 128 states), no Pallas kernel.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..compiler.re_dfa import DFA
from ..compiler.ruleset import CompiledRuleSet
from ..models.waf_model import WafModel, build_model, lgroup_onehot, post_match
from ..ops.dfa import DFABank, scan_dfa_bank, stack_dfas
from ..ops.transforms import apply_device_pipeline


def make_mesh(n_data: int, n_rule: int = 1, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    need = n_data * n_rule
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.array(devices[:need]).reshape(n_data, n_rule)
    return Mesh(grid, ("data", "rule"))


def _never_dfa() -> DFA:
    return DFA(
        trans=np.zeros((1, 1), dtype=np.int32),
        emit=np.zeros((1, 1), dtype=bool),
        match_end=np.zeros(1, dtype=bool),
        classmap=np.zeros(256, dtype=np.int32),
        always_match=False,
    )


def _stack_shard_banks(shard_dfas: list[list[DFA]]) -> DFABank:
    """Build per-shard banks with a common [S, C] layout (so the dense
    matmul tables share one shape and packing multiplier), then stack every
    leaf onto a leading shard axis."""
    s_max = max(d.n_states for dfas in shard_dfas for d in dfas)
    shard_banks = [stack_dfas(dfas, min_states=s_max) for dfas in shard_dfas]
    c_max = max(b.packed.shape[2] for b in shard_banks)

    def pad_c(b: DFABank):
        packed = np.asarray(b.packed)
        if packed.shape[2] < c_max:
            packed = np.pad(
                packed, ((0, 0), (0, 0), (0, c_max - packed.shape[2]))
            )
        return packed

    return DFABank(
        packed=jnp.asarray(np.stack([pad_c(b) for b in shard_banks])),
        classmap=jnp.asarray(np.stack([np.asarray(b.classmap) for b in shard_banks])),
        match_end=jnp.asarray(np.stack([np.asarray(b.match_end) for b in shard_banks])),
        always=jnp.asarray(np.stack([np.asarray(b.always) for b in shard_banks])),
        t256=jnp.stack([b.t256 for b in shard_banks]),
    )


@dataclass
class ShardedWafModel:
    """Rule-sharded model: stacked banks + a banks-free post-match model
    whose ``lgroup`` is remapped to the gathered layout.

    The conv-segment tier is **replicated** across rule shards (its
    kernel is tiny and its cost per target row is far below one DFA
    bank's); only the DFA banks shard over the rule axis. Global group
    order: segment blocks (sorted by pipeline) first, then sharded DFA
    buckets in gathered layout."""

    banks: list[DFABank]  # leaves carry leading [n_rule_shards] axis
    segs: list  # SegmentBlock, replicated
    post: WafModel  # banks == [] — post-match arrays only
    bank_pipelines: tuple  # pipeline id per bucket bank
    seg_pipelines: tuple
    bucket_widths: tuple  # groups-per-shard per bucket bank
    pipelines: tuple
    host_variant_index: tuple
    n_rule_shards: int = 1


def build_sharded_model(crs: CompiledRuleSet, n_rule_shards: int) -> ShardedWafModel:
    base = build_model(crs)  # reuse routing/arrays; we re-stack the banks

    # Re-route the groups exactly like build_model (segment tier first),
    # but split each DFA bucket across rule shards with never-match padding.
    from ..compiler.segments import plan_segments
    from ..models.waf_model import _STATE_BUCKETS
    from ..ops.segment import build_segment_block

    seg_groups: dict[int, list[tuple[int, object]]] = {}
    buckets: dict[tuple[int, int], list[int]] = {}
    for gid, grp in enumerate(crs.groups):
        pid = crs.group_pipeline[gid]
        plan = plan_segments(grp.dfa.ast)
        if plan is not None:
            seg_groups.setdefault(pid, []).append((gid, plan))
            continue
        s = grp.dfa.n_states
        bucket = next(b for b in _STATE_BUCKETS if s <= b)
        buckets.setdefault((pid, bucket), []).append(gid)

    remap = np.zeros(max(1, len(crs.groups)), dtype=np.int64)
    offset = 0
    segs = []
    seg_pipelines: list[int] = []
    for pid in sorted(seg_groups):
        items = seg_groups[pid]
        segs.append(build_segment_block([plan for _, plan in items]))
        seg_pipelines.append(pid)
        for g, _ in items:
            remap[g] = offset
            offset += 1

    banks: list[DFABank] = []
    bank_pipelines: list[int] = []
    bucket_widths: list[int] = []
    for (pid, _bucket), gids in sorted(buckets.items()):
        width = max(1, math.ceil(len(gids) / n_rule_shards))
        shard_dfas = []
        for s in range(n_rule_shards):
            chunk = gids[s * width : (s + 1) * width]
            dfas = [crs.groups[g].dfa for g in chunk]
            dfas += [_never_dfa()] * (width - len(dfas))
            for j, g in enumerate(chunk):
                # Gathered layout: bucket-major, then shard, then slot.
                remap[g] = offset + s * width + j
            shard_dfas.append(dfas)
        banks.append(_stack_shard_banks(shard_dfas))
        bank_pipelines.append(pid)
        bucket_widths.append(width)
        offset += n_rule_shards * width

    # lgroup in the ORIGINAL compiled link order, remapped to gathered ids.
    rl = int(base.lgroup.shape[0])
    lgroup = np.zeros(rl, dtype=np.int32)
    for i, link in enumerate(crs.links):
        lgroup[i] = remap[link.group] if link.group >= 0 else 0
    e_lg = lgroup_onehot(lgroup, max(1, offset))

    # The long-buffer fallback banks are replicated (like the seg tier):
    # they ride inside `post` so the shard_map body can reach them. Their
    # columns land in seg order via seg_perm — identical leading layout
    # in both the single-chip and gathered group orders (segs first).
    post = WafModel(
        banks=[],
        segs=[],
        long_banks=base.long_banks,
        seg_perm=base.seg_perm,
        long_bank_pipelines=base.long_bank_pipelines,
        ltype=base.ltype,
        lneg=base.lneg,
        lgroup=jnp.asarray(lgroup),
        lnumvar=base.lnumvar,
        lcmp=base.lcmp,
        lcmparg=base.lcmparg,
        lcounter=base.lcounter,
        inc=base.inc,
        exc=base.exc,
        e_lg=jnp.asarray(e_lg),
        m_count=base.m_count,
        link_count=base.link_count,
        e_numvar=base.e_numvar,
        e_counter=base.e_counter,
        removal=base.removal,
        has_removals=base.has_removals,
        link_matrix=base.link_matrix,
        link_mask=base.link_mask,
        decision=base.decision,
        status=base.status,
        order_key=base.order_key,
        phase=base.phase,
        weights=base.weights,
        counter_base=base.counter_base,
        seg_pipelines=(),
        pipelines=base.pipelines,
        pipeline_device=base.pipeline_device,
        host_variant_index=base.host_variant_index,
        engine_on=base.engine_on,
        detection_only=base.detection_only,
    )

    return ShardedWafModel(
        banks=banks,
        segs=segs,
        post=post,
        bank_pipelines=tuple(bank_pipelines),
        seg_pipelines=tuple(seg_pipelines),
        bucket_widths=tuple(bucket_widths),
        pipelines=base.pipelines,
        host_variant_index=base.host_variant_index,
        n_rule_shards=n_rule_shards,
    )


def eval_waf_sharded(mesh: Mesh, model: ShardedWafModel, tensors: tuple):
    """Evaluate stacked per-data-shard tensors over the mesh.

    ``tensors`` leaves carry a leading [n_data] axis; bank leaves carry a
    leading [n_rule] axis. Output leaves carry [n_data]."""
    n_rule = model.n_rule_shards

    from ..ops.segment import match_segment_block

    # jax.shard_map landed as a top-level API after 0.4.x; older jaxlibs
    # (the pinned CI/bench image ships 0.4.37) expose it under
    # jax.experimental only.
    if hasattr(jax, "shard_map"):
        _shard_map = jax.shard_map
    else:  # pragma: no cover - version-dependent import path
        from jax.experimental.shard_map import shard_map as _shard_map

    @partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P("rule"), P(), P(), P("data")),
        out_specs=P("data"),
    )
    def run(banks, segs, post, shard_tensors):
        banks = jax.tree.map(lambda x: x[0], banks)  # squeeze rule block
        (data, lengths, k1, k2, k3, req_id, numvals, vdata, vlengths) = jax.tree.map(
            lambda x: x[0], shard_tensors
        )  # squeeze data block
        transformed = {}

        def transformed_for(pid):
            if pid not in transformed:
                slot = model.host_variant_index[pid]
                if slot >= 0:
                    transformed[pid] = (vdata[slot], vlengths[slot])
                else:
                    transformed[pid] = apply_device_pipeline(
                        data, lengths, model.pipelines[pid]
                    )
            return transformed[pid]

        # Segment tier: replicated (identical on every rule shard). Long
        # shape buckets take the same constant-memory DFA fallback as the
        # single-chip path — the shared helper keys the budget off the
        # per-shard shape, which is the per-device bitmap that matters.
        from ..models.waf_model import segment_tier_hits

        seg_cols = segment_tier_hits(
            segs,
            model.seg_pipelines,
            post.long_banks,
            model.post.long_bank_pipelines,
            post.seg_perm,
            data,
            transformed_for,
        )

        per_bucket = []
        for bank, pid in zip(banks, model.bank_pipelines):
            per_bucket.append(scan_dfa_bank(bank, *transformed_for(pid)))
        t = data.shape[0]
        cols = list(seg_cols)
        if per_bucket:
            sub = jnp.concatenate(per_bucket, axis=1)  # [T, sum(width)]
            # The one collective: per-target hit bits across rule shards (ICI).
            gathered = jax.lax.all_gather(sub, "rule")  # [R, T, W]
            o = 0
            for width in model.bucket_widths:
                blk = gathered[:, :, o : o + width]  # [R, T, w]
                cols.append(jnp.moveaxis(blk, 0, 1).reshape(t, n_rule * width))
                o += width
        group_hits = (
            jnp.concatenate(cols, axis=1)
            if cols
            else jnp.zeros((t, 1), dtype=bool)
        )  # [T, G_gathered]
        out = post_match(post, group_hits, k1, k2, k3, req_id, numvals)
        # Post-gather values are identical on every rule shard; an idempotent
        # pmax makes that replication explicit to the vma type system.
        out = jax.tree.map(
            lambda x: jax.lax.pmax(x.astype(jnp.int32), "rule").astype(x.dtype), out
        )
        return jax.tree.map(lambda x: x[None], out)  # restore data axis

    return run(model.banks, model.segs, model.post, tensors)


@dataclass
class ShardedWafEngine:
    """Facade: WafEngine semantics over a device mesh."""

    compiled: CompiledRuleSet
    mesh: Mesh
    model: ShardedWafModel = field(init=False)

    def __post_init__(self):
        from ..engine.waf import WafEngine

        self.model = build_sharded_model(
            self.compiled, self.mesh.shape["rule"]
        )
        self._single = WafEngine(self.compiled)  # reuses extractor/tensorize

    def evaluate(self, requests):
        """Shard requests over the data axis, evaluate, reassemble verdicts
        in input order."""
        d = self.mesh.shape["data"]
        shards = [requests[i::d] for i in range(d)]
        extractions = [
            [self._single.extractor.extract(r) for r in shard] for shard in shards
        ]
        per_shard = [self._single._tensorize(ex) for ex in extractions]
        # Pad every shard's tensors to common shapes, then stack on axis 0.
        stacked = []
        for leaf_idx in range(len(per_shard[0])):
            leaves = [np.asarray(ts[leaf_idx]) for ts in per_shard]
            shape = tuple(max(l.shape[i] for l in leaves) for i in range(leaves[0].ndim))
            padded = []
            for ts, leaf in zip(per_shard, leaves):
                pad = [(0, s - ls) for s, ls in zip(shape, leaf.shape)]
                if leaf_idx == 5:  # req_id: pad rows must stay out-of-range
                    n_req = np.asarray(ts[6]).shape[0]
                    padded.append(
                        np.pad(leaf, pad, constant_values=n_req)
                    )
                else:
                    padded.append(np.pad(leaf, pad))
            stacked.append(jnp.asarray(np.stack(padded)))
        out = eval_waf_sharded(self.mesh, self.model, tuple(stacked))
        interrupted = np.asarray(out["interrupted"])
        status = np.asarray(out["status"])
        rule_index = np.asarray(out["rule_index"])

        from ..engine.waf import Verdict

        verdicts: list[Verdict | None] = [None] * len(requests)
        for s, shard in enumerate(shards):
            for j, _req in enumerate(shard):
                ridx = int(rule_index[s, j])
                verdicts[s + j * d] = Verdict(
                    interrupted=bool(interrupted[s, j]),
                    status=int(status[s, j]),
                    rule_id=int(self._single._rule_ids[ridx]) if ridx >= 0 else None,
                )
        return verdicts
