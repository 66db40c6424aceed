"""Host fallback evaluator: full verdict pipeline with zero JAX/XLA.

Degraded-mode serving (docs/DEGRADED_MODE.md) requires that the sidecar
returns a CORRECT verdict even when the accelerator path cannot — while
the first XLA compile of a CRS-scale model is still in flight (minutes
per matcher shape; five bench rounds produced zero graded verdicts
because nothing else could answer), or after the circuit breaker opened
on a device fault storm.

This evaluator reuses every host-side compiled artifact as-is:

- target extraction: the SAME ``TargetExtractor`` the device path uses;
- transforms: the reference host implementations
  (``compiler/transforms_host.py``) — the device kernels are
  differential-tested against exactly these, so bytes agree;
- matching: the flat-slot scalar walk (``ops/dfa_host.py``) over the
  SAME ``compiler/re_dfa.py`` tables the device banks stack;
- post-match: a NumPy mirror of ``models/waf_model.post_match`` —
  incidence, link AND-chains, ctl removals, anomaly counters (two-pass
  when needed), first-match-wins verdict. All integer/bool ops, so it
  is exact by construction (the device path's bf16/f32 matmul tricks
  are themselves exactness-preserving reformulations of these ops).

Verdicts are bit-identical to ``WafEngine.evaluate`` on the same
requests — pinned by tests/test_degraded_mode.py over the ftw crs-lite
corpus. Throughput is single-core NumPy (orders below the TPU path);
the point is a correct answer NOW, not a fast one.
"""

from __future__ import annotations

import numpy as np

from ..compiler.ruleset import (
    CompiledRuleSet,
    DEC_DENY,
    DEC_DROP,
    DEC_REDIRECT,
    LINK_ALWAYS,
    LINK_COUNTER,
    LINK_NEVER,
    LINK_NUMERIC,
    LINK_STRING,
)
from ..compiler.transforms_host import apply_pipeline
from ..ops.dfa_host import HostFlatDFA
from ..utils import get_logger
from .request import HttpRequest, TargetExtractor
from .waf import Verdict

log = get_logger("engine.host_fallback")

_BIG = 2**31 - 1
_MIN_LEN = 32


def _np_compare(cmp: np.ndarray, left: np.ndarray, right: np.ndarray) -> np.ndarray:
    """NumPy twin of models.waf_model._compare (codes from operators)."""
    out = np.zeros(np.broadcast(left, right).shape, dtype=bool)
    for code, fn in (
        (0, np.equal),
        (1, np.not_equal),
        (2, np.greater_equal),
        (3, np.greater),
        (4, np.less_equal),
        (5, np.less),
    ):
        m = cmp == code
        if m.any():
            out = np.where(m, fn(left, right), out)
    return out


class HostFallbackEvaluator:
    """Scalar-DFA + NumPy post-match evaluation of a CompiledRuleSet."""

    def __init__(self, crs: CompiledRuleSet, extractor: TargetExtractor | None = None):
        self.crs = crs
        self.extractor = extractor if extractor is not None else TargetExtractor(crs)

        # Per-pipeline flat walk tables over the ORIGINAL group order (no
        # device remap needed: links reference original group ids here).
        self._pipe_groups: list[tuple[int, list[int], HostFlatDFA]] = []
        by_pipe: dict[int, list[int]] = {}
        for gid, pid in enumerate(crs.group_pipeline):
            by_pipe.setdefault(pid, []).append(gid)
        for pid in sorted(by_pipe):
            gids = by_pipe[pid]
            self._pipe_groups.append(
                (pid, gids, HostFlatDFA([crs.groups[g].dfa for g in gids]))
            )

        # Link arrays — same layout rules as models/waf_model.build_model
        # (rl/rr padded to >= 1; pad links are LINK_NEVER, pad rules have
        # decision 0 / order_key BIG / phase 99), minus the group remap.
        rl = max(1, len(crs.links))
        k = crs.vocab.n_kinds
        self._ltype = np.full(rl, LINK_NEVER, dtype=np.int32)
        self._lneg = np.zeros(rl, dtype=bool)
        self._lgroup = np.zeros(rl, dtype=np.int32)
        self._lnumvar = np.zeros(rl, dtype=np.int32)
        self._lcmp = np.zeros(rl, dtype=np.int32)
        self._lcmparg = np.zeros(rl, dtype=np.int32)
        self._lcounter = np.zeros(rl, dtype=np.int32)
        self._inc = np.zeros((k, rl), dtype=bool)
        self._exc = np.zeros((k, rl), dtype=bool)
        for i, link in enumerate(crs.links):
            self._ltype[i] = link.link_type
            self._lneg[i] = link.negated
            if link.link_type == LINK_STRING:
                self._lgroup[i] = link.group
                for kid in link.include_kinds:
                    self._inc[kid, i] = True
                for kid in link.exclude_kinds:
                    self._exc[kid, i] = True
            self._lnumvar[i] = max(0, link.numvar)
            self._lcmp[i] = link.cmp
            self._lcmparg[i] = link.cmp_arg
            self._lcounter[i] = max(0, link.counter)

        rr = max(1, len(crs.rules))
        self._m_count = np.zeros((rl, rr), dtype=np.int32)
        self._link_count = np.zeros(rr, dtype=np.int32)
        self._decision = np.zeros(rr, dtype=np.int32)
        self._status = np.zeros(rr, dtype=np.int32)
        self._order_key = np.full(rr, _BIG, dtype=np.int32)
        self._phase = np.full(rr, 99, dtype=np.int32)
        for i, rule in enumerate(crs.rules):
            self._link_count[i] = len(rule.link_ids)
            for lid in rule.link_ids:
                self._m_count[lid, i] += 1
            self._decision[i] = rule.decision
            self._status[i] = rule.status
            self._order_key[i] = rule.order_key
            self._phase[i] = rule.phase

        weights = (
            crs.weights if crs.weights.size else np.zeros((rr, 1), dtype=np.int32)
        )
        if weights.shape[0] != rr:
            padded = np.zeros((rr, weights.shape[1]), dtype=np.int32)
            padded[: weights.shape[0]] = weights
            weights = padded
        self._weights = weights.astype(np.int64)
        self._counter_base = (
            crs.counter_base if crs.counter_base.size else np.zeros(1, np.int32)
        ).astype(np.int64)

        # ctl:ruleRemoveById/ByTag removal matrix (mirror of build_model).
        self._removal = np.zeros((rr, rr), dtype=bool)
        for i, r in enumerate(crs.rules):
            if not r.ctl_remove_ranges and not r.ctl_remove_tags:
                continue
            for j, r2 in enumerate(crs.rules):
                if j == i or r2.order_key <= r.order_key:
                    continue
                hit = any(lo <= r2.rule_id <= hi for lo, hi in r.ctl_remove_ranges)
                if not hit and r.ctl_remove_tags:
                    hit = any(t in r2.tags for t in r.ctl_remove_tags)
                if hit:
                    self._removal[i, j] = True
        self._removal_rows = tuple(
            sorted(
                (
                    i
                    for i in range(rr)
                    if i < len(crs.rules) and self._removal[i].any()
                ),
                key=lambda i: crs.rules[i].order_key,
            )
        )
        self._two_pass_counters = any(
            any(crs.links[l].link_type == LINK_COUNTER for l in r.link_ids)
            and self._weights[i].any()
            for i, r in enumerate(crs.rules)
        )
        self._engine_active = crs.engine_mode == "On"

        self._n_real_rules = len(crs.rules)
        self._rule_ids = np.asarray(
            [r.rule_id for r in crs.rules] or [0], dtype=np.int64
        )
        self._rule_phase = {r.rule_id: r.phase for r in crs.rules}
        self._visible_counters = [
            (c, name)
            for c, name in enumerate(crs.counters)
            if not name.startswith("__")
        ]
        self._host_pipes = {pid for pid, dev in enumerate(crs.pipeline_device) if not dev}

    # -- public API ----------------------------------------------------------

    def evaluate(self, requests: list[HttpRequest]) -> list[Verdict]:
        """Mirror of ``WafEngine.evaluate`` (including the
        SecRequestBodyLimitAction Reject 413 path)."""
        if not requests:
            return []
        prog = self.crs.program
        rejected: dict[int, Verdict] = {}
        if prog.request_body_access and prog.request_body_limit_action == "Reject":
            over = [
                i
                for i, r in enumerate(requests)
                if len(r.body) > prog.request_body_limit
            ]
            if over:
                exs = [
                    self.extractor.extract(requests[i], phase1_only=True)
                    for i in over
                ]
                early = self._evaluate_extractions(exs, max_phase=1)
                for i, v in zip(over, early):
                    rejected[i] = (
                        v
                        if v.interrupted
                        else Verdict(interrupted=True, status=413, rule_id=None)
                    )
        live = [r for i, r in enumerate(requests) if i not in rejected]
        if not live:
            return [rejected[i] for i in range(len(requests))]
        extractions = [self.extractor.extract(r) for r in live]
        verdicts = self._evaluate_extractions(extractions, max_phase=2)
        if not rejected:
            return verdicts
        out: list[Verdict] = []
        it = iter(verdicts)
        for i in range(len(requests)):
            out.append(rejected[i] if i in rejected else next(it))
        return out

    def evaluate_one(self, request: HttpRequest) -> Verdict:
        return self.evaluate([request])[0]

    def evaluate_phased(self, requests: list[HttpRequest]) -> list[Verdict]:
        """Mirror of ``WafEngine.evaluate_phased`` (phase-1 on headers
        before body ingest)."""
        if not requests:
            return []
        pass1 = [self.extractor.extract(r, phase1_only=True) for r in requests]
        early = self._evaluate_extractions(pass1, max_phase=1)
        survivors = [i for i, v in enumerate(early) if not v.interrupted]
        if survivors:
            full = self.evaluate([requests[i] for i in survivors])
            for i, verdict in zip(survivors, full):
                early[i] = verdict
        return early

    def evaluate_response(self, request: HttpRequest, response) -> Verdict:
        ex = self.extractor.extract(request, response=response)
        return self._evaluate_extractions([ex], max_phase=4)[0]

    # -- pipeline ------------------------------------------------------------

    def _evaluate_extractions(self, extractions: list, max_phase: int) -> list[Verdict]:
        body_cap = max(_MIN_LEN, self.crs.program.request_body_limit)

        # Rows mirror WafEngine._tensorize: one row per (target, 3-kind
        # chunk); values capped at the body limit BEFORE any transform
        # (exactly where the device path caps them).
        rows: list[tuple[int, bytes, tuple[int, int, int]]] = []
        for i, ex in enumerate(extractions):
            for t in ex.targets:
                kinds = self.extractor.kind_ids(t)
                if not kinds:
                    continue
                for off in range(0, len(kinds), 3):
                    chunk = kinds[off : off + 3]
                    chunk += [0] * (3 - len(chunk))
                    rows.append((i, t.value[:body_cap], tuple(chunk)))

        b = len(extractions)
        nv = self.crs.numvars.n_vars
        numvals = np.zeros((b, nv), dtype=np.int32)
        for i, ex in enumerate(extractions):
            for key, value in ex.numerics.items():
                numvals[i, self.crs.numvars.vars[key]] = value

        t_rows = len(rows)
        g = max(1, len(self.crs.groups))
        hits = np.zeros((t_rows, g), dtype=bool)
        if t_rows:
            # Dedup raw values once (headers/UA/paths repeat constantly),
            # then transform + walk each pipeline over its unique set.
            raw_index: dict[bytes, int] = {}
            row_raw = np.zeros(t_rows, dtype=np.int64)
            raw_list: list[bytes] = []
            for r, (_ri, value, _kinds) in enumerate(rows):
                uid = raw_index.setdefault(value, len(raw_index))
                if uid == len(raw_list):
                    raw_list.append(value)
                row_raw[r] = uid
            for pid, gids, matcher in self._pipe_groups:
                names = list(self.crs.pipelines[pid])
                t_index: dict[bytes, int] = {}
                t_list: list[bytes] = []
                raw2t = np.zeros(len(raw_list), dtype=np.int64)
                for u, value in enumerate(raw_list):
                    tv = apply_pipeline(value, names)
                    if pid in self._host_pipes:
                        # Host variants are re-capped after the transform
                        # (WafEngine._tensorize does the same); device
                        # transforms only ever shrink, so no cap needed.
                        tv = tv[:body_cap]
                    tid = t_index.setdefault(tv, len(t_index))
                    if tid == len(t_list):
                        t_list.append(tv)
                    raw2t[u] = tid
                uh = matcher.search_values(t_list)  # [Ut, Gp]
                hits[:, np.asarray(gids)] = uh[raw2t[row_raw]]

        k1 = np.asarray([r[2][0] for r in rows], dtype=np.int64)
        k2 = np.asarray([r[2][1] for r in rows], dtype=np.int64)
        k3 = np.asarray([r[2][2] for r in rows], dtype=np.int64)
        req_id = np.asarray([r[0] for r in rows], dtype=np.int64)
        out = self._post_match(hits, k1, k2, k3, req_id, numvals, max_phase)
        return self._decode(out, b)

    def _post_match(self, hits, k1, k2, k3, req_id, numvals, max_phase: int):
        """NumPy mirror of ``models/waf_model.post_match`` — same stage
        structure, direct bool/int ops in place of the MXU matmul
        reformulations (which are exact, so results agree bit-for-bit)."""
        b = numvals.shape[0]
        rl = self._ltype.shape[0]
        t_rows = hits.shape[0]

        # 3: incidence + per-target link matches.
        if t_rows:
            gm = hits[:, self._lgroup]  # [T, Rl]
            rel = self._inc[k1] | self._inc[k2] | self._inc[k3]
            excl = self._exc[k1] | self._exc[k2] | self._exc[k3]
            str_t = rel & ~excl & (gm ^ self._lneg[None, :])
        else:
            str_t = np.zeros((0, rl), dtype=bool)

        # 4a: targets -> requests (any-reduce by req_id).
        m_str = np.zeros((b, rl), dtype=bool)
        if t_rows:
            np.logical_or.at(m_str, req_id, str_t)

        # 4b: numeric links.
        vals = numvals[:, self._lnumvar]  # [B, Rl]
        m_num = (
            _np_compare(self._lcmp[None, :], vals, self._lcmparg[None, :])
            ^ self._lneg[None, :]
        )
        m_always = np.broadcast_to(~self._lneg[None, :], (b, rl))
        m_never = np.broadcast_to(self._lneg[None, :], (b, rl))

        lt = self._ltype[None, :]
        link_m = np.select(
            [lt == LINK_STRING, lt == LINK_NUMERIC, lt == LINK_ALWAYS, lt == LINK_NEVER],
            [m_str, m_num, m_always, m_never],
            default=False,
        )

        def rules_from_links(lm: np.ndarray) -> np.ndarray:
            counts = lm.astype(np.int32) @ self._m_count
            return counts == self._link_count[None, :]

        prelim = rules_from_links(link_m)

        removed = None
        if self._removal_rows:
            removed = np.zeros_like(prelim)
            for c in self._removal_rows:
                fires = prelim[:, c] & ~removed[:, c]
                removed = removed | (fires[:, None] & self._removal[c][None, :])
            prelim = prelim & ~removed

        # 4c: anomaly counters + threshold links.
        counters = self._counter_base[None, :] + prelim.astype(np.int64) @ self._weights
        cvals = counters[:, self._lcounter]
        m_counter = (
            _np_compare(self._lcmp[None, :], cvals, self._lcmparg[None, :])
            ^ self._lneg[None, :]
        )
        link_m = np.where(lt == LINK_COUNTER, m_counter, link_m)
        matched = rules_from_links(link_m)
        if removed is not None:
            matched = matched & ~removed

        if self._two_pass_counters:
            extra = matched & ~prelim
            counters = counters + extra.astype(np.int64) @ self._weights
            cvals = counters[:, self._lcounter]
            m_counter = (
                _np_compare(self._lcmp[None, :], cvals, self._lcmparg[None, :])
                ^ self._lneg[None, :]
            )
            link_m = np.where(lt == LINK_COUNTER, m_counter, link_m)
            matched = rules_from_links(link_m)
            if removed is not None:
                matched = matched & ~removed

        # 5: verdict — first matched decision rule in phase order.
        in_scope = (self._decision[None, :] != 0) & (
            self._phase[None, :] <= max_phase
        )
        keys = np.where(matched & in_scope, self._order_key[None, :], _BIG)
        first_key = keys.min(axis=1)
        first_idx = keys.argmin(axis=1)
        has_decision = first_key < _BIG
        dec = self._decision[first_idx]
        interrupts = (dec == DEC_DENY) | (dec == DEC_DROP) | (dec == DEC_REDIRECT)
        interrupted = has_decision & interrupts & self._engine_active
        status = np.where(interrupted, self._status[first_idx], 200)
        rule_index = np.where(has_decision, first_idx, -1)
        return {
            "matched": matched,
            "interrupted": interrupted,
            "status": status,
            "rule_index": rule_index,
            "scores": counters,
        }

    def _decode(self, out, n_requests: int) -> list[Verdict]:
        """Mirror of ``WafEngine._decode_packed`` over the unpacked dict."""
        verdicts: list[Verdict] = []
        for i in range(n_requests):
            ridx = int(out["rule_index"][i])
            verdicts.append(
                Verdict(
                    interrupted=bool(out["interrupted"][i]),
                    status=int(out["status"][i]),
                    rule_id=int(self._rule_ids[ridx]) if ridx >= 0 else None,
                    matched_ids=[
                        int(self._rule_ids[j])
                        for j in np.flatnonzero(out["matched"][i])
                        if j < self._n_real_rules
                    ],
                    scores={
                        name: int(out["scores"][i, c])
                        for c, name in self._visible_counters
                    },
                )
            )
        return verdicts
