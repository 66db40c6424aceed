"""Shape-canonical executable reuse: the AOT compile cache.

Compilation, not evaluation, dominates a cold start: every distinct
tier-shape signature used to mint a fresh full-model compile. The fix is the fixed-table
idiom from SIMD DFA engines (Hyperflex, arXiv:2512.07123; in-memory
regex matching, arXiv:2209.05686): the *executable* is a function of the
**shape signature only** — tier buffer shapes, mask tuple, model table
shapes — and the ruleset's DFA/segment tables are runtime operands
swapped into it. Three layers implement that here:

1. **Canonical signatures** (:func:`batch_signature`): the pytree
   treedef + leaf ``(shape, dtype)`` avals of the evaluation arguments
   plus the static kwargs. ``WafModel.tree_flatten`` canonicalizes its
   aux (host-side ``block_kinds``/``block_cost`` are excluded) so two
   rulesets with the same bucketed layout hash to the same signature.
2. **In-process executable cache** (:class:`ExecutableCache` /
   ``EXEC_CACHE``): signature → AOT-compiled executable
   (``jit.lower(...).compile()``). Tenants and hot reloads
   sharing a signature reuse ONE executable; a reload on an
   unchanged signature performs zero XLA compiles. Hit/miss/compile-time
   counters back the ``cko_compile_cache_*`` metrics.
3. **Persistent compilation cache** (:func:`configure_persistent_cache`):
   JAX's on-disk cache keyed by HLO hash — cold *processes* warm-start
   from disk (ftw chunk children, CI runs, sidecar restarts). ``JAX_COMPILATION_CACHE_DIR`` wins when set; else
   ``CKO_COMPILE_CACHE_DIR`` / the flag; else one fixed in-checkout path.

Thread safety: lookups and stats are lock-protected; a miss compiles
outside the lock (compiles are minutes-long — serializing them behind a
mutex would stall every tenant), so two threads racing the same
signature may both compile once. The persistent cache makes the loser
cheap, and ``setdefault`` semantics keep exactly one resident winner.
"""

from __future__ import annotations

import os
import threading
import time

import jax
import numpy as np

from ..observability import device_scopes
from ..utils import get_logger

log = get_logger("engine.compile_cache")


def _salt_persistent_keys() -> None:
    """JAX strips an operation's metadata before it hashes a program for
    the persistent cache, and a ``jax.named_scope`` is metadata: without
    this an executable cached by a build with other scopes (or none)
    would be served back carrying that build's names, and
    ``device_scopes.count`` would read them. The registry's salt goes
    into every key of this process through JAX's own hook for it."""
    try:
        from jax._src import cache_key

        cache_key.custom_hook = lambda: device_scopes.CACHE_KEY_SALT
    except ImportError as err:  # a JAX without the hook: names may then be stale
        log.error("persistent compile cache keys not salted", err)

# Where the persistent cache goes, in order of precedence:
# 1. ``JAX_COMPILATION_CACHE_DIR`` — set from outside (an operator, a
#    test rig, a machine image that keeps a cache between runs). JAX
#    reads it itself; nothing here sets another directory over it,
#    whatever the flag or ``CKO_COMPILE_CACHE_DIR`` say.
# 2. ``--compile-cache-dir`` / ``CKO_COMPILE_CACHE_DIR`` — the repo's
#    own knob, shared by the sidecar entrypoint, ftw chunk children
#    and CI. ``"0"`` disables.
# 3. ``DEFAULT_CACHE_DIR`` — one fixed path inside the checkout (the
#    path is part of the cache key's neighbourhood: a directory that
#    moves never hits), used by entrypoints that want a cache whether or
#    not anyone configured one (``default=True``).
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR_ENV = "CKO_COMPILE_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_bench_cache",
)

_configured_dir: list[str] = []


def resolve_cache_dir(cache_dir: str | None = None, default: bool = False) -> str | None:
    """The directory the precedence above selects, or None when nothing
    selects one / the repo's knob says ``"0"``."""
    d = os.environ.get(JAX_CACHE_DIR_ENV, "")
    if d:
        return d  # verbatim: it is JAX's own setting, not ours to rewrite
    d = cache_dir if cache_dir is not None else os.environ.get(CACHE_DIR_ENV, "")
    if d == "0":
        return None
    if not d and default:
        d = DEFAULT_CACHE_DIR
    return os.path.abspath(d) if d else None


def configure_persistent_cache(
    cache_dir: str | None = None, default: bool = False
) -> str | None:
    """Point JAX's persistent compilation cache at the directory
    :func:`resolve_cache_dir` selects. Idempotent; returns the directory
    in effect, or None when unset/disabled.

    Thresholds drop to zero so every executable is eligible — the WAF
    model's per-tier executables are exactly the artifacts a cold
    process needs back, whatever their size or compile time. Every key
    carries the device scopes' salt (:func:`_salt_persistent_keys`).
    """
    _salt_persistent_keys()  # whichever directory holds the cache, JAX's own included
    d = resolve_cache_dir(cache_dir, default)
    if d is None:
        return _configured_dir[0] if _configured_dir else None
    if _configured_dir and _configured_dir[0] == d:
        return d
    try:
        os.makedirs(d, exist_ok=True)
        if not os.environ.get(JAX_CACHE_DIR_ENV):
            # JAX already holds the env's directory; only ours is set here.
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # jax initializes its cache object AT MOST ONCE, on the first
        # compile, so a process that already compiled something has the
        # None-dir cache latched and the update above would be silently
        # ignored (writes no-op, warm starts never happen). reset_cache()
        # drops the latch so the next compile re-initializes against the
        # new directory.
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
    except Exception as err:  # never let cache wiring break serving
        log.error("persistent compile cache unavailable", err, dir=d)
        return None
    _configured_dir[:] = [d]
    log.info("persistent compile cache enabled", dir=d)
    return d


# numpy builds a dtype's ``name`` anew on every read (a few Python calls
# each); a window's signature reads a dozen, a model's hundreds.
_DTYPE_NAMES: dict = {}


def _aval(leaf) -> tuple:
    dtype = np.result_type(leaf)
    name = _DTYPE_NAMES.get(dtype)
    if name is None:
        name = _DTYPE_NAMES.setdefault(dtype, dtype.name)
    return (tuple(np.shape(leaf)), name)


def batch_signature(args: tuple, static_kwargs: tuple) -> tuple:
    """Hashable shape signature of one evaluation call: the argument
    pytree's treedef (``WafModel`` aux is canonicalized — see its
    ``tree_flatten``) + every leaf's ``(shape, dtype)`` + the static
    kwargs. Two calls share an executable iff their signatures match."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_aval(l) for l in leaves), static_kwargs)


def model_signature(model) -> tuple:
    """The model's half of every stage key: ``batch_signature`` of the
    first argument alone. It walks every table of the model (hundreds of
    leaves at CRS scale, a millisecond of Python), so an engine computes
    it where it assigns its model and never under traffic."""
    return batch_signature((model,), ())


def stage_key(jitted, model_sig: tuple, operands: tuple, static_kwargs: dict) -> tuple:
    """The executable-cache key of ``jitted(model, *operands,
    **static_kwargs)``, composed from the model's signature and a walk
    over the window's own operands only."""
    name = getattr(jitted, "__name__", None) or str(jitted)
    return (
        name,
        model_sig,
        batch_signature(operands, tuple(sorted(static_kwargs.items()))),
    )


def _device_ops(compiled) -> dict | None:
    """``device_scopes.count`` of a compiled executable, or None: its
    text raising or unreadable is a boundary, never an exception into a
    compile."""
    try:
        return device_scopes.count(compiled.as_text())
    except Exception as err:
        log.error("device operations not counted", err)
        return None


def _model_digest(key: tuple) -> str:
    """Eight hex digits that tell one model's executables from
    another's of the same name within this process (a stage key's second
    member is the model's signature)."""
    try:
        return format(hash(key[1]) & 0xFFFFFFFF, "08x")
    except (IndexError, TypeError):  # a key of another make
        return ""


class ExecutableCache:
    """Signature-keyed registry of AOT-compiled executables."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[tuple, object] = {}
        self.hits = 0
        self.misses = 0
        # Seconds of XLA backend compilation (``lowered.compile()`` —
        # served from the persistent disk cache when warm) vs tracing
        # (``fn.lower`` — never disk-cached; bounded by signature reuse).
        self.compile_s = 0.0
        self.trace_s = 0.0
        # Calls that fell back to the plain jit dispatch path (an AOT
        # call rejected its arguments — should be zero in practice).
        self.bypasses = 0
        self._bypassed_keys: set[tuple] = set()
        # Compiles currently running. A staged-rollout candidate whose
        # budget blew is *abandoned* (Python cannot interrupt an XLA
        # compile) but its compile thread keeps running to completion —
        # deliberately, so the result still lands here and in the
        # persistent disk cache, making the next attempt at the same
        # ruleset cheap. This gauge is how an abandoned compile stays
        # visible instead of becoming a silent background CPU burn.
        self.inflight = 0
        # Collected windows by where their stages ran. Under lazy tier
        # compilation (CKO_LAZY_TIERS=1) a window with any stage whose
        # executable was not resident is answered by the host twins —
        # correct, but not the device: ``host_twin_windows`` is the only
        # counter that tells the two apart after the fact.
        self.device_windows = 0
        self.host_twin_windows = 0
        # {"platform", "kind", "count"} of the device that produced the
        # first all-device window's output; None until one was collected.
        self.device: dict | None = None
        # Bumped by ``clear()``: an engine's launch table (resolved
        # executables per window shape, ``WafEngine._dispatch_tiers``)
        # is good for one generation only.
        self.generation = 0
        # Windows an engine launched from its table / windows that took
        # the spec-by-spec path (first sight of a shape on that engine,
        # a stage not resident, a table dropped with its model).
        self.launch_plan_hits = 0
        self.launch_plan_misses = 0
        # What a launch of each resident ``cko_*`` executable is made of
        # (``device_scopes.count`` of its optimized HLO, once, at its
        # compile): key -> {"name", "model", "device_ops"}. ``device_ops``
        # is None where the text could not be had or read, and
        # ``scope_table_errors`` counts those.
        self._device_ops: dict[tuple, dict] = {}
        # What an executable's owner said of it (``describe``), listed
        # beside it: the engine says how a matcher's conv tier is cut to
        # its budget (``seg_plan``: ``SegTierPlan.summary``; None in the
        # listing where nobody said).
        self._described: dict[tuple, dict] = {}
        self.scope_table_errors = 0

    def note_window(self, out, on_device: bool) -> None:
        """Count one collected window (``WafEngine._collect``). ``out``
        is the window's not-yet-read-back output array: the first
        all-device window's array says which device this process runs
        on, with no question asked of JAX before the engine dispatched."""
        with self._lock:
            if not on_device:
                self.host_twin_windows += 1
                return
            self.device_windows += 1
            if self.device is not None:
                return
        dev = next(iter(out.devices()))
        self.device = {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        }

    def note_launch_plan(self, hit: bool) -> None:
        """Count one window's lookup in its engine's launch table."""
        with self._lock:
            if hit:
                self.launch_plan_hits += 1
            else:
                self.launch_plan_misses += 1

    # -- core ---------------------------------------------------------------

    def _lookup(self, key: tuple, count_hit: bool = True):
        """``count_hit=False`` is the probe/pre-warm peek: hits must
        count only real dispatches, or the flapping-breaker probe loop
        would inflate cko_compile_cache_hits_total with zero traffic."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and count_hit:
                self.hits += 1
            return entry

    def _compile(self, key: tuple, jitted, args: tuple, kwargs: dict):
        with self._lock:
            self.inflight += 1
        try:
            t0 = time.perf_counter()
            lowered = jitted.lower(*args, **kwargs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        finally:
            with self._lock:
                self.inflight -= 1
        name = getattr(jitted, "__name__", str(jitted))
        scoped = name.startswith(device_scopes.EXECUTABLE_PREFIX)
        device_ops = _device_ops(compiled) if scoped else None
        t3 = time.perf_counter()
        with self._lock:
            self.misses += 1
            self.trace_s += t1 - t0
            self.compile_s += t2 - t1
            # Keep exactly one resident executable per signature even if
            # two threads raced the compile.
            compiled = self._entries.setdefault(key, compiled)
            if scoped and key not in self._device_ops:
                self._device_ops[key] = {
                    "name": name,
                    "model": _model_digest(key),
                    "device_ops": device_ops,
                }
                self.scope_table_errors += device_ops is None
        log.info(
            "compiled executable",
            fn=name,
            trace_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            scopes_s=round(t3 - t2, 3),
            entries=len(self._entries),
        )
        return compiled

    def run(
        self,
        key: tuple,
        compiled,
        jitted,
        args: tuple,
        static_kwargs: dict,
    ):
        """Call a resolved executable (``WafEngine`` keeps them per
        window shape and calls here directly; nothing walks ``args``).
        Falls back to the plain jit dispatch on any AOT argument
        rejection (counted, logged once per key)."""
        try:
            out = compiled(*args)
        except (TypeError, ValueError) as err:
            with self._lock:
                self.bypasses += 1
                first = key not in self._bypassed_keys
                self._bypassed_keys.add(key)
            if first:  # once per key: a persistent rejection must not
                log.error("AOT call bypassed to jit dispatch", err)  # flood logs
            return jitted(*args, **static_kwargs)
        # Count the hit only AFTER the compiled call succeeded: a
        # persistently-rejecting signature must read as bypasses, not
        # as a 100%-hit cache, on the cko_compile_cache_* gauges.
        with self._lock:
            self.hits += 1
        return out

    def warm(
        self,
        jitted,
        args: tuple,
        static_kwargs: dict,
        key: tuple | None = None,
    ) -> bool:
        """AOT-lower and compile ``jitted(*args, **static_kwargs)``
        WITHOUT executing (the promotion-probe pre-warm; tables and
        window slabs are runtime operands — new values at the same
        shapes never retrace). Returns True when this call minted a new
        executable."""
        if key is None:
            key = stage_key(jitted, model_signature(args[0]), args[1:], static_kwargs)
        if self._lookup(key, count_hit=False) is not None:
            return False
        self._compile(key, jitted, args, static_kwargs)
        return True

    # -- introspection ------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "compile_s": round(self.compile_s, 3),
                "trace_s": round(self.trace_s, 3),
                "bypasses": self.bypasses,
                "inflight": self.inflight,
                "device_windows": self.device_windows,
                "host_twin_windows": self.host_twin_windows,
                "launch_plan_hits": self.launch_plan_hits,
                "launch_plan_misses": self.launch_plan_misses,
                "persistent_dir": _configured_dir[0] if _configured_dir else None,
                "executables": sorted(
                    (
                        {**e, "seg_plan": None, **self._described.get(key, {})}
                        for key, e in self._device_ops.items()
                    ),
                    key=lambda e: (e["name"], e["model"]),
                ),
                "scope_table_errors": self.scope_table_errors,
            }

    def describe(self, key: tuple, **meta) -> dict:
        """What the owner of the executable under ``key`` says of it,
        listed beside it in ``stats()["executables"]`` once it is
        resident (before or after its compile; dropped by ``clear``).
        Returns all that was said so far: without ``meta`` a lookup."""
        with self._lock:
            said = self._described.setdefault(key, {})
            said.update(meta)
            return dict(said)

    def scope_tables(self) -> list[dict]:
        """``{"name", "model", "table"}`` of every resident ``cko_*``
        executable: instruction name -> scope path
        (``device_scopes.table``), walked now from the executables'
        text and kept nowhere. ``table`` is None where that fails."""
        with self._lock:
            resident = [
                (meta, self._entries[key])
                for key, meta in self._device_ops.items()
                if key in self._entries
            ]
        out = []
        for meta, compiled in sorted(resident, key=lambda r: (r[0]["name"], r[0]["model"])):
            try:
                names = device_scopes.table(compiled.as_text())
            except Exception as err:  # boundary: a dump without this table
                log.error("scope table unavailable", err, fn=meta["name"])
                names = None
            out.append({"name": meta["name"], "model": meta["model"], "table": names})
        return out

    def snapshot(self) -> tuple[int, int, float]:
        """(hits, misses, compile_s) — for delta reporting."""
        with self._lock:
            return (self.hits, self.misses, self.compile_s)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._device_ops.clear()
            self._described.clear()
            self.generation += 1


# Process-wide singleton: tenants, reloads and the promotion probe all
# share it — that sharing IS the executable reuse.
EXEC_CACHE = ExecutableCache()
