"""Fault-injection harness for degraded-mode serving.

Every knob is an environment variable read AT USE TIME (no import-order
trap: a test can flip a knob between requests), and every injected
failure is deterministic given the knobs — the error-rate stream comes
from a seeded PRNG so a fault storm reproduces exactly.

Knobs (all default off):

- ``CKO_FAULT_COMPILE_STALL_S=<seconds>``: the device evaluation path of
  a NOT-yet-warmed engine sleeps this long before dispatching —
  simulating the minutes-long first XLA compile of a CRS-scale model
  (the exact condition that produced five rounds of null bench
  verdicts). Warmed engines are unaffected.
- ``CKO_FAULT_DEVICE_ERROR_RATE=<0..1>``: each device dispatch raises
  :class:`DeviceFault` with this probability (1.0 = every dispatch) —
  simulating the runtime's "TPU device error — often a kernel fault"
  failure mode. The sidecar's circuit breaker is driven by
  exactly these errors in tests.
- ``CKO_FAULT_DEVICE_ERROR_SEED=<int>``: PRNG seed for the error-rate
  stream (default 0).
- ``CKO_FAULT_CACHE_OUTAGE=1``: every cache-server poll fails with a
  connection error — simulating a cache-server outage mid-reload.
- ``CKO_FAULT_DEVICE_LOST=1``: every device dispatch raises
  :class:`DeviceLostFault` — a PERSISTENT device loss (the TPU runtime's
  ``DEVICE_LOST``/device-disappeared class, not a transient kernel
  fault). Drives the re-init-exhaustion → ``broken`` escalation path.
- ``CKO_FAULT_DEVICE_LOST_N=<n>``: the NEXT ``n`` device dispatches
  raise :class:`DeviceLostFault`, then the storm clears on its own — a
  device loss the runtime recovers from once the sidecar re-puts its
  arrays on a fresh backend (docs/RECOVERY.md device-loss state
  machine). Changing the knob's value re-arms the countdown.
- ``CKO_FAULT_POISON_MARKER=<bytes>``: a device dispatch raises
  :class:`DeviceFault` iff any request body in the window contains this
  marker — the deterministic "poison request" the quarantine bisector
  (``sidecar/quarantine.py``) isolates. Unlike the rate knob, clean
  windows are untouched, so the blast radius is exactly the marked
  requests.
- ``CKO_FAULT_DEVICE_HANG_S=<seconds>``: the NEXT device readback
  (``WafEngine.collect``) sleeps this long before returning — a one-shot
  hung execution the dispatch watchdog must abandon. Changing the
  knob's value re-arms the shot.
- ``CKO_FAULT_SHADOW_DIVERGE_RATE=<0..1>``: each shadow-verification
  window of a staged rollout (``sidecar/rollout.py``) is forced to read
  as diverged with this probability — simulating a
  semantically-wrong-but-analyzer-clean candidate whose verdicts drift
  from the serving engine's. Drives the auto-rollback invariant in
  tests/the chaos job. Seeded separately
  (``CKO_FAULT_SHADOW_DIVERGE_SEED``) so it never perturbs the
  device-error stream's reproducibility.

Adversarial *ingress* knobs (consumed by traffic generators —
``hack/ingest_fuzz.py`` and the chaos ``ingress-storm`` clients — to
shape the bytes they send at the frontends; the server never reads
them):

- ``CKO_FAULT_SLOW_CLIENT_DELAY_S=<seconds>``: clients pace their sends
  byte-group by byte-group with this inter-send delay (slowloris /
  slow-body simulation driving the 408 read deadlines).
- ``CKO_FAULT_CLIENT_RESET_RATE=<0..1>``: each request is abandoned
  mid-stream with a hard RST (SO_LINGER 0) with this probability.
- ``CKO_FAULT_CHUNK_TRUNCATE_RATE=<0..1>``: each chunked request ends
  truncated mid-chunk with this probability.
- ``CKO_FAULT_CHUNK_OVERSIZE_RATE=<0..1>``: each chunked request
  declares a chunk size past the body ceiling with this probability
  (driving the streaming 413).
- ``CKO_FAULT_CONN_STORM=<n>``: storm clients open this many extra
  concurrent connections (driving the global connection cap's 503).
- ``CKO_FAULT_INGRESS_SEED=<int>``: one shared PRNG seed for all the
  ingress-client draws above (default 0) — a storm replays exactly.

The hooks are called from production code (``engine/waf.py``,
``sidecar/reloader.py``) and are no-ops (a few ns of ``os.environ``
lookups) when the knobs are unset — the serving hot path never pays for
the harness.
"""

from __future__ import annotations

import os
import random
import threading
import time
import urllib.error


class DeviceFault(RuntimeError):
    """An injected device-path failure (stands in for the accelerator
    runtime's kernel faults and dropped dispatches). The sidecar's circuit
    breaker treats it exactly like a real device error."""


class DeviceLostFault(RuntimeError):
    """An injected DEVICE-LOST-class failure: the backend is gone, not
    merely faulting (XLA's ``DEVICE_LOST`` / device-disappeared errors).
    The sidecar's device-loss manager (docs/RECOVERY.md) treats it as
    grounds for a full array re-put on a fresh backend, distinct from
    the transient circuit breaker."""

    def __init__(self, msg: str = "DEVICE_LOST: injected device loss"):
        super().__init__(msg)


_lost_lock = threading.Lock()
_lost_remaining = 0
_lost_armed: str | None = None


def injected_device_lost() -> bool:
    """True when this dispatch should fail with a device loss.

    ``CKO_FAULT_DEVICE_LOST=1`` is persistent (every dispatch).
    ``CKO_FAULT_DEVICE_LOST_N=<n>`` arms a countdown: the next ``n``
    dispatches fail, then the storm clears — re-arming happens whenever
    the knob's VALUE changes (set it to a fresh number per scenario)."""
    global _lost_remaining, _lost_armed
    if os.environ.get("CKO_FAULT_DEVICE_LOST", "") not in ("", "0"):
        return True
    raw = os.environ.get("CKO_FAULT_DEVICE_LOST_N", "")
    with _lost_lock:
        if raw != _lost_armed:
            _lost_armed = raw
            try:
                _lost_remaining = max(0, int(raw or 0))
            except ValueError:
                _lost_remaining = 0
        if _lost_remaining > 0:
            _lost_remaining -= 1
            return True
    return False


_rng_lock = threading.Lock()
_rng: random.Random | None = None
_rng_seed: int | None = None


def _error_rng() -> random.Random:
    """Seeded PRNG for the device-error stream; reseeds when the seed
    knob changes so consecutive tests get independent, reproducible
    streams."""
    global _rng, _rng_seed
    seed = int(os.environ.get("CKO_FAULT_DEVICE_ERROR_SEED", "0"))
    with _rng_lock:
        if _rng is None or seed != _rng_seed:
            _rng = random.Random(seed)
            _rng_seed = seed
        return _rng


def injected_compile_stall_s() -> float:
    try:
        return float(os.environ.get("CKO_FAULT_COMPILE_STALL_S", "0") or 0)
    except ValueError:
        return 0.0


def injected_device_error() -> bool:
    """True when this dispatch should fail (consumes one PRNG draw)."""
    try:
        rate = float(os.environ.get("CKO_FAULT_DEVICE_ERROR_RATE", "0") or 0)
    except ValueError:
        return False
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    rng = _error_rng()
    with _rng_lock:
        return rng.random() < rate


def poison_marker() -> bytes | None:
    """The poison byte-marker, or None when the knob is unset
    (``CKO_FAULT_POISON_MARKER``). Engines fault a window iff any live
    request body contains the marker — the quarantine bisector's
    deterministic offender."""
    raw = os.environ.get("CKO_FAULT_POISON_MARKER", "")
    if not raw:
        return None
    return raw.encode("utf-8", "surrogateescape")


_hang_lock = threading.Lock()
_hang_armed: str | None = None
_hang_fired = False


def injected_device_hang_s() -> float:
    """One-shot readback hang (``CKO_FAULT_DEVICE_HANG_S``): the first
    call after the knob is set (or its value changes — re-arming works
    like ``CKO_FAULT_DEVICE_LOST_N``) returns the hang duration; every
    later call returns 0 until re-armed."""
    global _hang_armed, _hang_fired
    raw = os.environ.get("CKO_FAULT_DEVICE_HANG_S", "")
    with _hang_lock:
        if raw != _hang_armed:
            _hang_armed = raw
            _hang_fired = False
        if _hang_fired:
            return 0.0
        try:
            s = float(raw or 0)
        except ValueError:
            s = 0.0
        if s > 0:
            _hang_fired = True
            return s
    return 0.0


def on_device_dispatch(warmed: bool) -> None:
    """Called at the top of every device evaluation (engine/waf.py).

    Order matters: the stall runs first (a compiling engine blocks, then
    may fault), and the error check runs on every dispatch — warmed or
    not — because device fault storms hit steady-state serving too."""
    if not warmed:
        stall = injected_compile_stall_s()
        if stall > 0:
            time.sleep(stall)
    if injected_device_lost():
        raise DeviceLostFault()
    if injected_device_error():
        raise DeviceFault("injected device error (CKO_FAULT_DEVICE_ERROR_RATE)")


_shadow_rng_lock = threading.Lock()
_shadow_rng: random.Random | None = None
_shadow_rng_seed: int | None = None


def injected_shadow_diverge() -> bool:
    """True when this shadow window should be scored as diverged
    (``CKO_FAULT_SHADOW_DIVERGE_RATE``; consumes one draw from its own
    seeded PRNG — the device-error stream stays untouched)."""
    global _shadow_rng, _shadow_rng_seed
    try:
        rate = float(os.environ.get("CKO_FAULT_SHADOW_DIVERGE_RATE", "0") or 0)
    except ValueError:
        return False
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    seed = int(os.environ.get("CKO_FAULT_SHADOW_DIVERGE_SEED", "0"))
    with _shadow_rng_lock:
        if _shadow_rng is None or seed != _shadow_rng_seed:
            _shadow_rng = random.Random(seed)
            _shadow_rng_seed = seed
        return _shadow_rng.random() < rate


_ingress_rng_lock = threading.Lock()
_ingress_rng: random.Random | None = None
_ingress_rng_seed: int | None = None


def _ingress_rate(name: str) -> float:
    try:
        return float(os.environ.get(name, "0") or 0)
    except ValueError:
        return 0.0


def _ingress_draw(rate: float) -> bool:
    """One draw from the shared seeded ingress-client PRNG
    (``CKO_FAULT_INGRESS_SEED``; reseeds when the seed knob changes)."""
    global _ingress_rng, _ingress_rng_seed
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    seed = int(os.environ.get("CKO_FAULT_INGRESS_SEED", "0"))
    with _ingress_rng_lock:
        if _ingress_rng is None or seed != _ingress_rng_seed:
            _ingress_rng = random.Random(seed)
            _ingress_rng_seed = seed
        return _ingress_rng.random() < rate


def injected_client_delay_s() -> float:
    """Inter-send pacing for adversarial clients
    (``CKO_FAULT_SLOW_CLIENT_DELAY_S``; 0 = send at full speed)."""
    return max(0.0, _ingress_rate("CKO_FAULT_SLOW_CLIENT_DELAY_S"))


def injected_client_reset() -> bool:
    """True when this client request should abandon mid-stream with a
    hard reset (``CKO_FAULT_CLIENT_RESET_RATE``)."""
    return _ingress_draw(_ingress_rate("CKO_FAULT_CLIENT_RESET_RATE"))


def injected_chunk_truncate() -> bool:
    """True when this chunked request should end truncated mid-chunk
    (``CKO_FAULT_CHUNK_TRUNCATE_RATE``)."""
    return _ingress_draw(_ingress_rate("CKO_FAULT_CHUNK_TRUNCATE_RATE"))


def injected_chunk_oversize() -> bool:
    """True when this chunked request should declare a chunk past the
    body ceiling (``CKO_FAULT_CHUNK_OVERSIZE_RATE``)."""
    return _ingress_draw(_ingress_rate("CKO_FAULT_CHUNK_OVERSIZE_RATE"))


def injected_conn_storm() -> int:
    """Extra concurrent connections storm clients should open
    (``CKO_FAULT_CONN_STORM``; 0 = no storm)."""
    try:
        return max(0, int(os.environ.get("CKO_FAULT_CONN_STORM", "0") or 0))
    except ValueError:
        return 0


def cache_outage_active() -> bool:
    return os.environ.get("CKO_FAULT_CACHE_OUTAGE", "") not in ("", "0")


def maybe_cache_outage() -> None:
    """Called before every cache-server HTTP fetch (sidecar/reloader.py)."""
    if cache_outage_active():
        raise urllib.error.URLError(
            "injected cache-server outage (CKO_FAULT_CACHE_OUTAGE)"
        )
