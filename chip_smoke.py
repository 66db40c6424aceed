#!/usr/bin/env python3
"""Chip smoke: full crs-lite answered by the tpu-engine sidecar on one chip.

Drives the served path as its users start it — ``python -m
coraza_kubernetes_operator_tpu.cmd.tpu_engine`` with shipped defaults,
polling a ``RuleSetCacheServer`` — and passes only if every counted
verdict came from the device: status and ``x-waf-rule-id`` equal the plain
host evaluator's, and ``/waf/v1/stats`` shows no fallback, fail-open,
shed, abandoned, quarantined, cached or host-twin answer and no compile
across the counted pass.

    python chip_smoke.py                      # one TPU chip, full crs-lite
    python chip_smoke.py --allow-cpu ftw/rules/base.conf ftw/rules/crs-mini.conf

One process per chip: this parent never touches JAX (it imports only the
JAX-free ``cache`` subpackage); the sidecar child holds the device; the
helper child that computes the reference verdicts is pinned to the CPU.

Every line on stdout is one JSON object. Phase lines carry ``"phase"``
and ``"ok"``; the LAST line is ``{"ok": ..., "device": {"platform",
"kind", "count"}}`` with the device as the sidecar's first device window
reported it. ``ok`` is true only on a TPU. Any failed phase exits
non-zero and prints no last line. ``--allow-cpu`` is for rehearsal and
the tier-1 test: every phase still runs and must pass, the exit code
says whether they did, and the last line says ``"ok": false`` and
``"platform": "cpu"``.

Window shapes. A per-tier matcher executable for full crs-lite takes
minutes to compile for the chip, and the sidecar mints one per (unique
rows x width) bucket, so the smoke drives ONE matcher shape: U_STAR
unique rows at width L_STAR. Each burst is pipelined down one connection
in one write, which the async frontend parses without yielding — one
burst, one window. The helper sizes the bursts by asking the engine's
own tensorizer (with a replica of the cross-batch value cache) how many
unique uncached rows each burst holds; a fixed-length salted query value
on every request (an argument every rule set looks at) defeats the
verdict cache and in-window dedup and pins the width. Requests wider
than L_STAR are left out and counted.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / "build" / "chip_smoke"  # build/ is git-ignored
DEFAULT_RULES = REPO / "ftw" / "rules" / "crs-lite"
INSTANCE = "smoke/ruleset"
SEED = 0

# The one matcher shape the smoke drives (see module docstring).
U_STAR = 32  # unique uncached rows per window: bucket (16, 32]
L_STAR = 512  # window width: longest value in (256, 512]
MISS_LO, MISS_HI = 20, 30  # burst fill target, inside the bucket with slack
SALT_HEX = 300  # salted value length -> a row in (256, 512]; CRS caps an argument at 400

MIN_REQUESTS = 120  # per pass; small corpora cycle with fresh salts
MIN_EACH_VERDICT = 20
WARM_PASSES_MAX = 4
COUNTED_ATTEMPTS_MAX = 3

T_READY_S = 300.0
T_PROMOTE_S = 900.0
T_HELPER_S = 600.0
T_SETTLE_S = 900.0
T_BURST_S = 120.0
T_EXIT_S = 60.0

# Counters that must not move across the counted pass: each names a way
# a request can be answered without the device.
ZERO_GROWTH = (
    ("degraded", "fallback_requests"),
    ("failopen_total",),
    ("shed_total",),
    ("watchdog", "windows_abandoned"),
    ("quarantine", "isolated_total"),
    ("compile_cache", "bypasses"),
    ("compile_cache", "host_twin_windows"),
    ("compile_cache", "misses"),
    ("verdict_cache", "hits_total"),
    ("verdict_cache", "window_dedup_rows"),
    ("batcher", "errors"),
)


class SmokeFailure(Exception):
    def __init__(self, phase: str, why: str, **detail):
        super().__init__(f"{phase}: {why}")
        self.phase, self.why, self.detail = phase, why, detail


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=False), flush=True)


def dig(d: dict, path: tuple):
    for k in path:
        d = d[k]
    return d


# ---------------------------------------------------------------------------
# Helper child (CPU-pinned): reference verdicts + burst plan
# ---------------------------------------------------------------------------


def read_rules(paths: list[Path]) -> tuple[str, int]:
    """Concatenate the rule-set arguments. A directory is a CRS-layout
    tree in ``ftw/corpus.py:load_ruleset_text`` order (non-rule config
    first, then REQUEST-*/RESPONSE-* by family, SecDataDir pinned to its
    ``data/``); a file is taken as is. Returns (text, files read)."""
    parts: list[str] = []
    n_files = 0
    for path in paths:
        if path.is_dir():
            confs = sorted(path.glob("*.conf"))
            is_rule = lambda p: p.name.startswith(("REQUEST-", "RESPONSE-"))
            setup = [p for p in confs if not is_rule(p)]
            rules = sorted(
                (p for p in confs if is_rule(p)),
                key=lambda p: (p.name.split("-", 2)[1], p.name),
            )
            parts.append(f"SecDataDir {path / 'data'}")
            parts += [p.read_text() for p in setup + rules]
            n_files += len(setup) + len(rules)
        else:
            parts.append(path.read_text())
            n_files += 1
    return "\n".join(parts), n_files


def corpus_for(paths: list[Path]) -> Path:
    """``ftw/rules/<name>/`` is exercised by ``ftw/tests-<name>/``; loose
    ``.conf`` files by the bundled mini corpus ``ftw/tests/``."""
    for path in paths:
        if path.is_dir():
            return path.parent.parent / f"tests-{path.name}"
    return REPO / "ftw" / "tests"


def _salt(tag: str, n_hex: int) -> str:
    return hashlib.shake_256(f"{SEED}/{tag}".encode()).hexdigest(n_hex // 2)


def helper_main(job_path: str) -> int:
    """Runs in a child started with JAX_PLATFORMS=cpu, so importing the
    engine here cannot touch the chip. Writes the plan (every pass's wire
    bytes and the host evaluator's verdict for each request) and exits
    before the parent sends anything that counts."""
    job = json.loads(Path(job_path).read_text())
    assert os.environ.get("JAX_PLATFORMS") == "cpu", "helper must be CPU-pinned"

    import numpy as np

    from coraza_kubernetes_operator_tpu.engine.waf import WafEngine, warmup_request
    from coraza_kubernetes_operator_tpu.ftw.corpus import load_ruleset_text
    from coraza_kubernetes_operator_tpu.ftw.loader import load_tests_report
    from coraza_kubernetes_operator_tpu.sidecar import ingest

    text = Path(job["rules_file"]).read_text()
    rule_paths = [Path(p) for p in job["rules"]]
    if len(rule_paths) == 1 and rule_paths[0].is_dir():
        # The parent read the tree itself (it must not import the
        # loader); hold it to the loader's order here.
        assert text == load_ruleset_text(rule_paths[0]), "rule order drifted"
    engine = WafEngine(text)
    tests, unparsable = load_tests_report(job["corpus"])
    assert not unparsable, f"unparsable corpus files: {unparsable}"

    skipped = {"response_stage": 0, "framing": 0, "too_wide": 0, "unplaced": 0}
    framing = {
        "content-length", "transfer-encoding", "connection", "expect",
        "x-cko-deadline-ms", "x-waf-tenant", "traceparent",
    }

    def wire_safe(stage) -> bool:
        # Pipelining needs honest framing, and the lane/engine routing
        # must be the default one: leave out the few corpus requests
        # that carry their own framing or routing headers.
        if stage.version != "HTTP/1.1" or not stage.uri or stage.uri != stage.uri.strip():
            return False
        if stage.method.encode() not in ingest._KNOWN_METHODS:
            return False
        if any(c.isspace() or ord(c) < 0x21 or c == "#" for c in stage.uri):
            return False
        if stage.uri.startswith(ingest.API_PREFIX):
            return False
        for k, v in stage.headers:
            if k.lower() in framing or not k or k != k.strip():
                return False
            if any(c in "\r\n\0" for c in k + v) or ":" in k:
                return False
        return True

    def build(stage, tag: str):
        """(wire bytes, HttpRequest as the sidecar will see it)."""
        headers = list(stage.headers)
        if not any(k.lower() == "host" for k, _ in headers):
            headers.insert(0, ("Host", "localhost"))
        if stage.data:
            headers.append(("Content-Length", str(len(stage.data))))
        uri = stage.uri + ("&" if "?" in stage.uri else "?")
        uri += "ckosmoke=" + _salt(tag, SALT_HEX)
        head = f"{stage.method} {uri} HTTP/1.1\r\n" + "".join(
            f"{k}: {v}\r\n" for k, v in headers
        ) + "\r\n"
        head_b = head.encode("utf-8", "surrogateescape")
        method, target, version, pairs, _special = ingest._parse_head(head_b)
        body = stage.data if method != b"GET" else b""
        req = ingest._materialize(
            method, target.decode("latin-1", "replace"), version, pairs, body,
            b"127.0.0.1",
        )
        return head_b + stage.data, req

    def probe(reqs):
        """(fits the one shape, unique uncached rows, their cache keys)."""
        tiers, _nv, masks, _cached, miss_keys, lease = engine._batch_tensors(reqs)
        if lease is not None:
            lease.release()
        _u, length = tiers[0][0].shape
        n_miss = len(miss_keys[0])
        one_shape = len(tiers) == 1 and masks[0] is None and length == L_STAR
        return one_shape and n_miss <= MISS_HI, n_miss, miss_keys[0]

    assert engine.value_cache is not None, "value cache is a shipped default"

    def remember(keys) -> None:
        # Replica of the sidecar's cross-batch value cache: only WHICH
        # keys are present matters for shapes, not the hit rows.
        if keys:
            engine.value_cache.insert(
                keys, np.zeros((len(keys), engine.value_cache.packed_len), np.uint8)
            )

    stages = []
    for t in tests:
        for s in t.stages:
            if s.response_status is not None:
                skipped["response_stage"] += 1
            elif not wire_safe(s):
                skipped["framing"] += 1
            elif not probe([build(s, "width-probe")[1]])[0]:
                skipped["too_wide"] += 1
            else:
                body = s.data if s.method != "GET" else b""
                stages.append(("bulk" if body else "interactive", s))
    assert stages, "no usable request in the corpus"
    reps = -(-MIN_REQUESTS // len(stages))
    base = [st for _ in range(reps) for st in stages]

    def plan(tag: str, pool, fixed=None):
        """Greedy single-lane bursts whose unique uncached rows land in
        [MISS_LO, MISS_HI]. ``fixed`` replays an earlier composition
        (list of index lists) with this pass's salts and re-checks it."""
        bursts, layout = [], []
        serial = itertools.count(1)
        idxs, built, fill = [], [], [0, []]  # the open burst: (misses, keys)

        def grow(i) -> bool:
            """Add pool[i] to the open burst if the burst still fits."""
            one = build(pool[i][1], f"{tag}/{next(serial)}")
            ok, n_miss, keys = probe([r for _, r in built] + [one[1]])
            if ok:
                idxs.append(i)
                built.append(one)
                fill[:] = [n_miss, keys]
            return ok

        def close() -> None:
            assert fill[0] > U_STAR // 2, (tag, idxs, fill[0])
            remember(fill[1])
            bursts.append((pool[idxs[0]][0], list(built)))
            layout.append(list(idxs))
            del idxs[:], built[:]
            fill[:] = [0, []]

        if fixed is not None:
            for burst in fixed:
                assert all(grow(i) for i in burst), (tag, burst)
                close()
            return bursts, layout
        for lane in ("interactive", "bulk"):
            for i in (i for i, (ln, _) in enumerate(pool) if ln == lane):
                if not grow(i):
                    # Full, or pool[i] alone is too much: close what is
                    # open if it may be closed, and try once more.
                    if fill[0] > U_STAR // 2:
                        close()
                    if idxs or not grow(i):
                        skipped["unplaced"] += 1
                        continue
                if fill[0] >= MISS_LO:
                    close()
            # Top the last burst up with requests already placed (their
            # values are cached by now: each adds only its salt rows).
            placed = iter([i for b in layout for i in b if pool[i][0] == lane] * 4)
            while idxs and fill[0] < MISS_LO:
                grow(next(placed))
            if idxs:
                close()
        return bursts, layout

    # The sidecar's promotion probe sends this one first; so do we.
    remember(probe([warmup_request()])[2])
    passes = {}
    # Cold pass: fills the value cache; every burst still lands on the
    # one matcher shape, so nothing else is ever minted.
    passes["prime"], prime_layout = plan("prime", base)
    kept = [base[i] for idxs in prime_layout for i in idxs]
    # Steady composition, planned once and replayed with fresh salts.
    passes["warm0"], steady = plan("warm0", kept)
    for name in [f"warm{i}" for i in range(1, WARM_PASSES_MAX)] + [
        f"counted{i}" for i in range(COUNTED_ATTEMPTS_MAX)
    ]:
        passes[name], _ = plan(name, kept, fixed=steady)

    out = {"passes": {}}
    for name, bursts in passes.items():
        rows = []
        for lane, built in bursts:
            verdicts = engine.host_fallback.evaluate([r for _, r in built])
            rows.append(
                {
                    "lane": lane,
                    "requests": [
                        {
                            "wire": base64.b64encode(w).decode(),
                            "status": v.status if v.interrupted else 200,
                            "rule_id": str(v.rule_id or 0) if v.interrupted else None,
                        }
                        for (w, _), v in zip(built, verdicts)
                    ],
                }
            )
        out["passes"][name] = rows
    report = engine.compiled.report
    out["info"] = {
        "corpus_tests": len(tests),
        "requests_per_pass": len(kept),
        "base_requests": len(stages),
        "skipped_requests": skipped,
        "rules_compiled": len(engine.rule_meta),
        "rules_skipped": len(report.skipped),
        "native_tensorizer": bool(engine._native.available),
    }
    tmp = Path(job["plan_file"] + ".tmp")
    tmp.write_text(json.dumps(out))
    tmp.replace(job["plan_file"])
    return 0


# ---------------------------------------------------------------------------
# Parent (never touches JAX)
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Sidecar:
    def __init__(self, port: int, proc: subprocess.Popen, log_path: Path):
        self.port, self.proc, self.log_path = port, proc, log_path

    def get(self, path: str, timeout: float = 10.0) -> tuple[int, bytes]:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{self.port}{path}", timeout=timeout
            ) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def stats(self) -> dict:
        status, body = self.get("/waf/v1/stats")
        if status != 200:
            raise SmokeFailure("stats", f"/waf/v1/stats answered {status}")
        return json.loads(body)

    def alive(self, phase: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(phase, f"sidecar exited with code {rc}", log=self.tail())

    def tail(self, n: int = 30) -> list[str]:
        try:
            return self.log_path.read_text(errors="replace").splitlines()[-n:]
        except OSError:
            return []

    def wait_for(self, phase: str, limit_s: float, what: str, pred):
        """Poll ``pred()`` (truthy = done) under a wall-clock limit that
        fails naming the stage it was waiting on."""
        deadline = time.monotonic() + limit_s
        last = None
        while time.monotonic() < deadline:
            self.alive(phase)
            try:
                last = pred()
                if last:
                    return last
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(
            phase, f"gave up after {limit_s:.0f}s waiting for {what}", log=self.tail()
        )

    def settle(self, phase: str) -> dict:
        """Wait until no compile is running or queued: ``inflight`` 0 and
        ``misses`` unchanged on two polls in a row."""
        seen = [None]

        def quiet():
            cc = self.stats()["compile_cache"]
            now = (cc["inflight"], cc["misses"])
            was, seen[0] = seen[0], now
            return now[0] == 0 and was == now

        self.wait_for(phase, T_SETTLE_S, "compiles to finish (compile_cache.inflight)", quiet)
        return self.stats()


def send_burst(port: int, wires: list[bytes]) -> list[tuple[int, str | None]]:
    """Pipeline one burst down one connection in one write; read the
    replies in order. Returns (status, x-waf-rule-id) per request."""
    out = []
    with socket.create_connection(("127.0.0.1", port), timeout=T_BURST_S) as sock:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.sendall(b"".join(wires))
        f = sock.makefile("rb")
        for _ in wires:
            line = f.readline()
            if not line:
                raise OSError("connection closed mid-burst")
            status = int(line.split()[1])
            rule_id, length = None, 0
            while True:
                h = f.readline()
                if h in (b"\r\n", b"\n", b""):
                    break
                k, _, v = h.decode("latin-1").partition(":")
                if k.lower() == "x-waf-rule-id":
                    rule_id = v.strip()
                elif k.lower() == "content-length":
                    length = int(v)
            f.read(length)
            out.append((status, rule_id))
    return out


def run_pass(sc: Sidecar, name: str, bursts: list[dict]) -> tuple[dict, dict]:
    """Send one pass (same order, one burst in flight) and compare every
    reply with the host evaluator's verdict. Returns the pass's report
    line and the stats read after it."""
    before = sc.stats()
    t0 = time.monotonic()
    sent = blocked = allowed = 0
    for bi, burst in enumerate(bursts):
        wires = [base64.b64decode(r["wire"]) for r in burst["requests"]]
        try:
            replies = send_burst(sc.port, wires)
        except (OSError, ValueError, IndexError) as err:
            sc.alive(name)
            raise SmokeFailure(name, f"burst {bi} failed: {err!r}", log=sc.tail())
        for ri, (want, got) in enumerate(zip(burst["requests"], replies)):
            if got != (want["status"], want["rule_id"]):
                raise SmokeFailure(
                    name,
                    "verdict differs from the host evaluator's",
                    burst=bi, request=ri,
                    want=[want["status"], want["rule_id"]], got=list(got),
                    request_line=base64.b64decode(want["wire"]).split(b"\r\n", 1)[0].decode("latin-1"),
                )
            sent += 1
            blocked += got[0] != 200
            allowed += got[0] == 200
    wall = time.monotonic() - t0
    after = sc.stats()
    growth = {".".join(p): dig(after, p) - dig(before, p) for p in ZERO_GROWTH}
    lanes = {
        lane: after["lanes"][lane]["windows_total"] - before["lanes"][lane]["windows_total"]
        for lane in ("interactive", "bulk")
    }
    return {
        "phase": name,
        "sent": sent,
        "blocked": blocked,
        "allowed": allowed,
        "bursts": {
            lane: sum(1 for b in bursts if b["lane"] == lane) for lane in lanes
        },
        "windows": lanes,
        "batcher_requests": after["batcher"]["requests"] - before["batcher"]["requests"],
        "device_windows": after["compile_cache"]["device_windows"]
        - before["compile_cache"]["device_windows"],
        "native_windows": after["native"]["windows_total"] - before["native"]["windows_total"],
        "growth": growth,
        "inflight": [before["compile_cache"]["inflight"], after["compile_cache"]["inflight"]],
        "breaker": after["degraded"]["breaker"]["state"],
        "wall_s": round(wall, 3),
    }, after


# A counted attempt that met a new window shape is void, not failed:
# these are the counters that say so.
MINTED = ("compile_cache.misses", "compile_cache.host_twin_windows")


def minted_a_shape(r: dict) -> bool:
    return any(r["growth"][k] for k in MINTED) or r["inflight"] != [0, 0]


def check_counted(r: dict, after: dict) -> list[str]:
    """Everything the counted pass must show; returns what it did not."""
    bad = []
    if r["blocked"] < MIN_EACH_VERDICT or r["allowed"] < MIN_EACH_VERDICT:
        bad.append(f"need {MIN_EACH_VERDICT} blocked and allowed, got {r['blocked']}/{r['allowed']}")
    if r["batcher_requests"] != r["sent"]:
        bad.append(f"batcher.requests grew {r['batcher_requests']}, sent {r['sent']}")
    for lane, n in r["windows"].items():
        if n <= 0:
            bad.append(f"lanes.{lane}.windows_total did not grow")
    for name, delta in r["growth"].items():
        if delta != 0:
            bad.append(f"{name} grew by {delta}")
    if r["inflight"] != [0, 0]:
        bad.append(f"compile_cache.inflight {r['inflight']}")
    if r["breaker"] != "closed":
        bad.append(f"breaker {r['breaker']}")
    if r["device_windows"] + r["growth"]["compile_cache.host_twin_windows"] != sum(
        r["windows"].values()
    ):
        bad.append(f"device_windows grew {r['device_windows']}, windows {r['windows']}")
    if not (after["native"]["available"] and after["native"]["tiered"]):
        bad.append("native window pipeline not in use (native.available/tiered)")
    if r["native_windows"] <= 0:
        bad.append("native.windows_total did not grow")
    if after["serving_mode"] != "promoted":
        bad.append(f"serving_mode {after['serving_mode']}")
    return bad


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "rules", nargs="*", type=Path, default=[DEFAULT_RULES],
        help="rule set: a CRS-layout directory or .conf files, concatenated"
        " in order (default: ftw/rules/crs-lite, the full crs-lite tree)",
    )
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="rehearsal: run and check every phase on whatever device JAX"
        " finds; the last line still says ok=false unless that is a TPU",
    )
    args = ap.parse_args(argv)

    # The cache server is the one part of the program this process runs
    # itself; the subpackage is JAX-free. Importing it is also what fails
    # when chip_smoke.py is run without the program around it.
    from coraza_kubernetes_operator_tpu.cache import RuleSetCache, RuleSetCacheServer

    held = os.environ.get("JAX_PLATFORMS", "")
    if held and "tpu" not in held.split(",") and not args.allow_cpu:
        emit({"phase": "device", "ok": False,
              "error": f"JAX_PLATFORMS={held} holds JAX off the chip"})
        return 1

    t_start = time.monotonic()
    WORK.mkdir(parents=True, exist_ok=True)
    cache_server = None
    sidecar: Sidecar | None = None
    helper = None
    try:
        # -- native library, from the committed source ----------------------
        t0 = time.monotonic()
        lib = WORK / "libcko_native.so"
        build_log = WORK / "native_build.log"
        with open(build_log, "wb") as fh:
            rc = subprocess.call(
                ["make", "-C", str(REPO / "native"), f"TARGET={lib}"],
                stdout=fh, stderr=subprocess.STDOUT,
            )
        if rc != 0 or not lib.exists():
            raise SmokeFailure(
                "native_build", f"make exited {rc}",
                log=build_log.read_text(errors="replace").splitlines()[-20:],
            )
        emit({"phase": "native_build", "ok": True,
              "seconds": round(time.monotonic() - t0, 2)})

        # -- rule set into a cache server ------------------------------------
        rule_paths = [p.resolve() for p in args.rules]
        text, n_files = read_rules(rule_paths)
        rules_file = WORK / "rules.conf"
        rules_file.write_text(text)
        cache = RuleSetCache()
        cache_server = RuleSetCacheServer(cache, host="127.0.0.1", port=0)
        cache_server.start()
        entry = cache.put(INSTANCE, text)
        emit({
            "phase": "ruleset", "ok": True,
            "rules": [str(p.relative_to(REPO)) if p.is_relative_to(REPO) else str(p)
                      for p in rule_paths],
            "files": n_files, "bytes": len(text),
            "secrule_directives": sum(
                1 for ln in text.splitlines() if ln.lstrip().startswith("SecRule ")
            ),
            "reduced": [], "uuid": entry.uuid,
        })

        # -- the one chip-holding child: the sidecar, shipped defaults -------
        env = dict(os.environ, CKO_NATIVE_LIB=str(lib))
        port = free_port()
        log_path = WORK / "sidecar.log"
        t_child = time.monotonic()
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "coraza_kubernetes_operator_tpu.cmd.tpu_engine",
                "--cache-server-instance", INSTANCE,
                "--cache-server-cluster", f"127.0.0.1:{cache_server.port}",
                "--bind-address", "127.0.0.1", "--port", str(port),
            ],
            cwd=REPO, env=env, stdout=open(log_path, "wb"), stderr=subprocess.STDOUT,
        )
        sidecar = Sidecar(port, proc, log_path)

        # -- the helper child, pinned to the CPU ------------------------------
        job = {
            "rules_file": str(rules_file), "rules": [str(p) for p in rule_paths],
            "corpus": str(corpus_for(rule_paths)), "plan_file": str(WORK / "plan.json"),
        }
        (WORK / "job.json").write_text(json.dumps(job))
        Path(job["plan_file"]).unlink(missing_ok=True)
        helper_log = WORK / "helper.log"
        helper = subprocess.Popen(
            [sys.executable, "-c",
             "import sys, chip_smoke; sys.exit(chip_smoke.helper_main(sys.argv[1]))",
             str(WORK / "job.json")],
            cwd=REPO, env=dict(env, JAX_PLATFORMS="cpu"),
            stdout=open(helper_log, "wb"), stderr=subprocess.STDOUT,
        )

        # -- ready, then promoted ---------------------------------------------
        sidecar.wait_for("ready", T_READY_S, "/waf/v1/readyz to answer 200",
                         lambda: sidecar.get("/waf/v1/readyz")[0] == 200)
        emit({"phase": "ready", "ok": True,
              "seconds": round(time.monotonic() - t_child, 2)})

        def promoted():
            s = sidecar.stats()
            return (s["serving_mode"] == "promoted"
                    and s["compile_cache"]["inflight"] == 0) and s

        s = sidecar.wait_for("promotion", T_PROMOTE_S,
                             "serving_mode promoted with compile_cache.inflight 0", promoted)
        device = s["device"]
        emit({
            "phase": "promotion", "ok": True,
            "cold_wall_to_promotion_s": round(time.monotonic() - t_child, 2),
            "rules_skipped": s["cko_rules_skipped_total"],
            "rules_approximated": s["cko_rules_approximated_total"],
            "automata": {k: s["automata"].get(k) for k in (
                "enabled", "tiers", "dfa_hot_blocks", "prefilter_blocks",
                "flat_bins", "flat_slots", "flat_groups", "per_bank_kernels")},
            "dfa_states": [s["compile_cache"]["dfa_states_pre_min"],
                           s["compile_cache"]["dfa_states_post_min"]],
            "tier_compile_s": s["compile_cache"]["tier_compile_s"],
            "persistent_dir": s["compile_cache"]["persistent_dir"],
            "native": {k: s["native"][k] for k in ("available", "tiered")},
        })
        if not device or (device["platform"] != "tpu" and not args.allow_cpu):
            raise SmokeFailure("device", "the sidecar's first device window did not run on a TPU",
                               device=device)
        emit({"phase": "device", "ok": True, "device": device})

        # -- the plan: helper must be gone before anything counts -------------
        try:
            rc = helper.wait(timeout=T_HELPER_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("helper", f"gave up after {T_HELPER_S:.0f}s waiting for the reference verdicts")
        if rc != 0:
            raise SmokeFailure("helper", f"helper exited {rc}",
                               log=helper_log.read_text(errors="replace").splitlines()[-20:])
        plan = json.loads(Path(job["plan_file"]).read_text())
        emit({"phase": "helper", "ok": True, **plan["info"],
              "window_shape": {"unique_rows": U_STAR, "width": L_STAR}})
        if not plan["info"]["native_tensorizer"]:
            raise SmokeFailure("helper", "helper planned without the native tensorizer")

        # -- prime (cold value cache), then warm until a pass mints nothing ---
        r, _ = run_pass(sidecar, "prime", plan["passes"]["prime"])
        emit({**r, "ok": True})
        sidecar.settle("prime")
        for i in range(WARM_PASSES_MAX):
            r, _ = run_pass(sidecar, f"warm{i}", plan["passes"][f"warm{i}"])
            emit({**r, "ok": True, "clean": not minted_a_shape(r)})
            sidecar.settle(f"warm{i}")
            if not minted_a_shape(r):
                break
        else:
            raise SmokeFailure("warm", f"still compiling after {WARM_PASSES_MAX} warm passes")

        # -- counted ------------------------------------------------------------
        for i in range(COUNTED_ATTEMPTS_MAX):
            r, after = run_pass(sidecar, f"counted{i}", plan["passes"][f"counted{i}"])
            bad = check_counted(r, after)
            emit({**r, "ok": not bad, "failed_checks": bad})
            if not bad:
                break
            void = [f"{k} grew by {r['growth'][k]}" for k in MINTED if r["growth"][k]]
            void.append(f"compile_cache.inflight {r['inflight']}")
            if not minted_a_shape(r) or set(bad) - set(void):
                raise SmokeFailure("counted", "; ".join(bad))
            sidecar.settle(f"counted{i}")  # a new shape was minted: go again
        else:
            raise SmokeFailure("counted", f"no compile-free attempt in {COUNTED_ATTEMPTS_MAX}")
        cc = after["compile_cache"]
        emit({
            "phase": "served", "ok": True,
            "exec_signatures": cc["exec_signatures"], "executables": cc["entries"],
            "compile_s": cc["compile_s"], "trace_s": cc["trace_s"],
            "tier_compile_s": cc["tier_compile_s"],
            "cache_hits": cc["hits"], "cache_misses": cc["misses"],
            "host_twin_windows_total": cc["host_twin_windows"],
            "device_windows_total": cc["device_windows"],
            "native_p50_window_ms": after["native"].get("p50_window_ms"),
            "native_p50_assemble_ms": after["native"].get("p50_assemble_ms"),
            "batcher": {k: after["batcher"][k] for k in (
                "p50_step_ms", "p99_step_ms", "p50_host_stage_ms", "p50_device_stage_ms",
                "mean_batch_size")},
            "prefilter": after["automata"].get("prefilter"),
        })
        device = after["device"]

        # -- SIGTERM: the drain path must end in exit code 0 --------------------
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=T_EXIT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("shutdown", f"sidecar still running {T_EXIT_S:.0f}s after SIGTERM",
                               log=sidecar.tail())
        if rc != 0:
            raise SmokeFailure("shutdown", f"sidecar exited {rc} on SIGTERM", log=sidecar.tail())
        emit({"phase": "shutdown", "ok": True, "exit_code": rc,
              "total_wall_s": round(time.monotonic() - t_start, 2)})
    except SmokeFailure as f:
        emit({"phase": f.phase, "ok": False, "error": f.why, **f.detail})
        return 1
    finally:
        for p in (helper, sidecar.proc if sidecar else None):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
        if cache_server is not None:
            cache_server.stop()

    emit({"ok": device["platform"] == "tpu", "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
