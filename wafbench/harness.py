"""One run of one cell: start the system under test, warm the cell's
window shapes, drive the measured window from the client's side of the
socket, decide ``correct``, read the layers.

The process that runs this never imports JAX: the sidecar child holds
the chip, and the trace is reduced by a CPU-pinned child after the
sidecar has gone. From the program this file takes the JAX-free
``cache`` subpackage (the RuleSet cache server the sidecar polls is part
of the deployment), the shipped ``cmd.tpu_engine`` command (through
``wafbench/sidecar_launch.py``) and the counters of ``/waf/v1/stats``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

from . import arith

REPO = Path(__file__).resolve().parents[1]
WORK = REPO / "build" / "wafbench"  # build/ is git-ignored
INSTANCE = "wafbench/ruleset"
# What the harness states itself on the sidecar's command line: a
# configuration's ``sidecar_args`` may name none of these.
HARNESS_FLAGS = ("--cache-server-instance", "--cache-server-cluster", "--bind-address",
                 "--port", "--compile-cache-dir")

T_READY_S = 300.0
T_PROMOTE_S = 900.0
T_SETTLE_S = 900.0
T_BURST_S = 120.0
T_EXIT_S = 60.0
T_CONTROL_S = 120.0
# The profiler's two commands wait under a limit of their own: a process's
# first stop_trace converts the whole capture on the host and has read
# 108-117 s in a CRS cell (PERF.md section 7), which is no fault of the run.
T_TRACE_S = 480.0
TRACE_COMMANDS = ("trace_start", "trace_stop")
WARM_ROUNDS_MAX = 6
TRACE_SECONDS = 4.0  # the interval the device numbers come from, Python tracer off
TRACE_PY_SECONDS = 1.5  # a second interval, Python tracer on, only to name the idle gaps
DEVICE_WINDOWS = "compile_cache.device_windows"  # one a device window, whatever its shape

# A warm round that met a new window shape is repeated, not failed.
MINTED = ("compile_cache.misses", "compile_cache.host_twin_windows")

# What one device window that the watchdog abandons moves, with every verdict
# right: the host fallback answers its requests, the bisector probes its halves
# (a shape of their own: one miss, one host twin). A machine that stands still
# for a second does that to a sound program (PERF.md section 2), so these are
# held to a share of the window's requests or device windows and not to 0; a
# mix's other ``zero_growth`` counters stay exact.
OFF_PATH_SHARE = 0.01
OFF_PATH = {"degraded.fallback_requests": "requests", "batcher.errors": "requests",
            "watchdog.windows_abandoned": "windows", "compile_cache.misses": "windows",
            "compile_cache.host_twin_windows": "windows"}


class RunFailure(Exception):
    def __init__(self, phase: str, why: str, **detail):
        super().__init__(f"{phase}: {why}")
        self.phase, self.why, self.detail = phase, why, detail


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def dig(d: dict, dotted: str):
    for k in dotted.split("."):
        d = d[k]
    return d


def module_name(metric: str) -> str:
    """File name of a metric's reader: the name with ``.`` and ``-`` as ``_``."""
    return metric.replace(".", "_").replace("-", "_")


def read_rules(rules: Path) -> str:
    """A configuration's rule text: one file as it is, or a CRS-layout
    tree in the order the program's loader reads one (non-rule config
    first, then REQUEST-*/RESPONSE-* by family, SecDataDir pinned to its
    ``data/``)."""
    if rules.is_file():
        return rules.read_text()
    confs = sorted(rules.glob("*.conf"))
    is_rule = lambda p: p.name.startswith(("REQUEST-", "RESPONSE-"))
    families = sorted((p for p in confs if is_rule(p)),
                      key=lambda p: (p.name.split("-", 2)[1], p.name))
    parts = [f"SecDataDir {rules.resolve() / 'data'}"]
    parts += [p.read_text() for p in [p for p in confs if not is_rule(p)] + families]
    return "\n".join(parts)


# -- the cell, found by name ----------------------------------------------------


def load_by_path(path: Path):
    """A generator or a reader, found as a file by its name: a later PR
    adds one by adding the file, and edits nothing."""
    spec = importlib.util.spec_from_file_location(f"wafbench_found_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads``, with its configuration, mix, generator
    and readers, all found by name under ``root`` (the checkout)."""

    def __init__(self, workload: str, root: Path = REPO):
        self.root = root
        self.bench = json.loads((root / "BENCHMARK.json").read_text())
        found = [w for w in self.bench["workloads"] if w["name"] == workload]
        if not found:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.workload = found[0]
        config = next(c for c in self.bench["configs"] if c["name"] == self.workload["config"])
        self.config_dir = (root / config["file"]).parent
        self.config = json.loads((root / config["file"]).read_text())
        self.bench_dir = root / self.bench["paths"][0]
        self.mix = json.loads(
            (self.bench_dir / "traffic" / f"{self.workload['traffic']}.json").read_text())

    def metrics(self, group: str) -> list[dict]:
        name = self.workload["name"]
        return [m for m in self.bench[group] if name in m.get("workloads", [name])]

    def instances(self) -> list[dict]:
        """The deployment's RuleSets, the default tenant first: the
        configuration's ``instances``, or its one ``rules`` under
        ``INSTANCE``."""
        return self.config.get("instances") or [
            {"instance": INSTANCE, "rules": self.config["rules"]}]

    def rules_texts(self, control: bool = False) -> dict[str, str]:
        """Instance name -> rule text, in the order deployed. The
        control edits one instance's text (``control.instance``, else
        the first) and leaves the others as they are."""
        texts = {i["instance"]: read_rules(self.config_dir / i["rules"])
                 for i in self.instances()}
        if control:
            edit = self.config["control"]
            name = edit.get("instance", next(iter(texts)))
            if name not in texts:
                raise SystemExit(f"control: no instance {name!r} in the configuration")
            text = texts[name]
            for old, new in edit["replace"]:
                if old not in text:
                    raise SystemExit(f"control: {old!r} not in the rule text")
                text = text.replace(old, new)
            texts[name] = text + "\n" + edit.get("append", "") + "\n"
        return texts

    def rules_text(self, control: bool = False) -> str:
        """The default tenant's rule text."""
        return next(iter(self.rules_texts(control).values()))

    def sidecar_args(self) -> list[str]:
        """The configuration's own arguments to the sidecar, refused
        where one (spelt out, abbreviated or with ``=``) is a flag the
        harness sets itself."""
        args = self.config.get("sidecar_args", [])
        if not isinstance(args, list) or not all(isinstance(a, str) for a in args):
            raise RunFailure("sidecar_args", "sidecar_args is not a list of strings")
        for a in args:
            flag = a.split("=", 1)[0]
            owned = [f for f in HARNESS_FLAGS if len(flag) > 2 and f.startswith(flag)]
            if owned:
                raise RunFailure("sidecar_args", f"{a!r} sets {owned[0]}, which the harness sets",
                                 harness_flags=list(HARNESS_FLAGS))
        return list(args)

    def sidecar_argv(self, cache_port: int, port: int, compile_cache_dir) -> list[str]:
        """The shipped command's arguments for this deployment: the
        harness's own, then the configuration's."""
        argv = ["--cache-server-instance", ",".join(i["instance"] for i in self.instances()),
                "--cache-server-cluster", f"127.0.0.1:{cache_port}",
                "--bind-address", "127.0.0.1"]
        if compile_cache_dir is not None:
            argv += ["--compile-cache-dir", str(compile_cache_dir)]
        return argv + ["--port", str(port)] + self.sidecar_args()

    def not_loaded(self, stats: dict) -> list[str]:
        """The deployment's instances that ``/waf/v1/stats`` does not
        show serving a rule set."""
        tenants = stats.get("tenants", {})
        return [i["instance"] for i in self.instances()
                if not tenants.get(i["instance"].strip("/"), {}).get("loaded")]

    def traffic(self, seed: int):
        gen = load_by_path(self.bench_dir / "generators" / f"{self.mix['generator']}.py")
        return gen.Traffic(self.config_dir, self.mix, seed)

    def reader(self, metric: str):
        return load_by_path(self.bench_dir / "layer_metrics" / f"{module_name(metric)}.py")


# -- the sidecar child ------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Sidecar:
    def __init__(self, port: int, proc: subprocess.Popen, log_path: Path, control: Path):
        self.port, self.proc, self.log_path, self.control = port, proc, log_path, control
        self._n = 0

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
            raise RunFailure("stats", f"/waf/v1/stats answered {status}")
        return json.loads(body)

    def alive(self, phase: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise RunFailure(phase, f"sidecar exited with code {rc}", log=self.tail())

    def tail(self, n: int = 30) -> list[str]:
        try:
            return self.log_path.read_text(errors="replace").splitlines()[-n:]
        except OSError:
            return []

    def wait_for(self, phase: str, limit_s: float, what: str, pred, poll_s: float = 0.25,
                 **detail):
        began = time.monotonic()
        while time.monotonic() < began + limit_s:
            self.alive(phase)
            try:
                last = pred()
                if last:
                    return last
            except (OSError, ValueError):
                pass
            time.sleep(poll_s)
        raise RunFailure(phase, f"gave up after {limit_s:.0f}s waiting for {what}",
                         limit_s=limit_s, waited_s=round(time.monotonic() - began, 3),
                         **detail, log=self.tail())

    def settle(self, phase: str) -> dict:
        """No compile running or queued: ``inflight`` 0 and ``misses``
        unchanged on two polls in a row."""
        seen = [None]

        def quiet():
            cc = self.stats()["compile_cache"]
            now = (cc["inflight"], cc["misses"])
            was, seen[0] = seen[0], now
            return now[0] == 0 and was == now

        self.wait_for(phase, T_SETTLE_S, "compiles to finish (compile_cache.inflight)", quiet)
        return self.stats()

    def command(self, *words: str, poll_s: float = 0.25) -> dict:
        """One command to the launcher's control thread; its answer."""
        self._n += 1
        name = f"{words[0]}-{self._n}"
        self.proc.stdin.write((" ".join([words[0], name, *words[1:]]) + "\n").encode())
        self.proc.stdin.flush()
        answer = self.control / f"{name}.json"
        limit_s = T_TRACE_S if words[0] in TRACE_COMMANDS else T_CONTROL_S
        self.wait_for(words[0], limit_s, f"the launcher to answer {words[0]}", answer.exists,
                      poll_s=poll_s, command=words[0])
        out = json.loads(answer.read_text())
        if "error" in out:
            raise RunFailure(words[0], out["error"])
        return out


# -- the client -------------------------------------------------------------------


def read_replies(sock: socket.socket, buf: bytearray, n: int) -> list[tuple]:
    """Read ``n`` pipelined replies: (status, x-waf-rule-id, time read).
    Replies that arrive in one segment share its time."""
    out = []
    pos = 0
    now = time.perf_counter()
    while len(out) < n:
        end = buf.find(b"\r\n\r\n", pos)
        if end < 0:
            chunk = sock.recv(1 << 18)
            if not chunk:
                raise OSError("connection closed mid-burst")
            now = time.perf_counter()
            buf += chunk
            continue
        head = bytes(buf[pos:end]).split(b"\r\n")
        status = int(head[0].split(None, 2)[1])
        rule_id, length = None, 0
        for h in head[1:]:
            k, _, v = h.partition(b":")
            k = k.lower()
            if k == b"x-waf-rule-id":
                rule_id = v.strip().decode("latin-1")
            elif k == b"content-length":
                length = int(v)
        body_end = end + 4 + length
        while len(buf) < body_end:
            chunk = sock.recv(1 << 18)
            if not chunk:
                raise OSError("connection closed mid-body")
            now = time.perf_counter()
            buf += chunk
        pos = body_end
        out.append((status, rule_id, now))
    del buf[:pos]
    return out


class Client(threading.Thread):
    """One keep-alive connection in a closed loop: one burst in flight,
    each burst pipelined in one write. Runs until ``until()`` says stop;
    a burst is begun only before that and is always read to its end."""

    def __init__(self, port: int, traffic, conn: int, go: threading.Event, until):
        super().__init__(name=f"wafbench-client-{conn}", daemon=True)
        self.port, self.traffic, self.conn, self.go, self.until = port, traffic, conn, go, until
        self.records: list[tuple] = []  # (sent at, read at, as expected)
        self.bursts = 0
        self.error: str | None = None
        self.unanswered = 0

    def run(self) -> None:
        stream = self.traffic.stream(self.conn)
        tag = f"c{self.conn}"
        try:
            with socket.create_connection(("127.0.0.1", self.port), timeout=T_BURST_S) as sock:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                buf = bytearray()
                burst = next(stream)
                wire = self.traffic.salted(burst, tag)
                self.go.wait()
                while not self.until():
                    t_sent = time.perf_counter()
                    self.unanswered = burst.n
                    sock.sendall(wire)
                    sent = burst
                    # Salt the next burst while this one is being served.
                    burst = next(stream)
                    wire = self.traffic.salted(burst, tag)
                    replies = read_replies(sock, buf, sent.n)
                    self.unanswered = 0
                    self.bursts += 1
                    self.records += [
                        (t_sent, t_read, (status, rule_id) == want)
                        for (status, rule_id, t_read), want in zip(replies, sent.expected)
                    ]
        except (OSError, ValueError, IndexError) as err:
            self.error = repr(err)


def drive(sc: Sidecar, traffic, seconds: float | None) -> dict:
    """All of the mix's connections at once. With ``seconds`` a timed
    window; without, a warm round in which every connection sends each
    of its bursts once."""
    go = threading.Event()
    t_end = [float("inf")]
    clients = []
    for c in range(len(traffic.connections)):
        if seconds is None:
            quota = len(traffic.connections[c])
            until = lambda q=quota, c=c: clients[c].bursts >= q
        else:
            until = lambda: time.perf_counter() >= t_end[0]
        clients.append(Client(sc.port, traffic, c, go, until))
    for cl in clients:
        cl.start()
    time.sleep(0.05)  # every client holds its first burst ready
    t0 = time.perf_counter()
    if seconds is not None:
        t_end[0] = t0 + seconds
    go.set()
    return {"clients": clients, "t0": t0, "t_end": t_end}


def join(window: dict, phase: str, sc: Sidecar) -> None:
    for cl in window["clients"]:
        cl.join(timeout=T_BURST_S + 30)
        if cl.is_alive():
            raise RunFailure(phase, "a client did not finish", log=sc.tail())


def send_sequential(sc: Sidecar, traffic, bursts, tag: str) -> tuple[int, int]:
    """Bursts one after another down one connection (the prime pass).
    Returns (requests, replies that differ from the reference)."""
    n = bad = 0
    with socket.create_connection(("127.0.0.1", sc.port), timeout=T_BURST_S) as sock:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        buf = bytearray()
        for burst in bursts:
            sock.sendall(traffic.salted(burst, tag))
            for (status, rule_id, _t), want in zip(read_replies(sock, buf, burst.n), burst.expected):
                n += 1
                bad += (status, rule_id) != want
    return n, bad


def window_numbers(w: dict) -> dict:
    """The window's arithmetic. A rate is over all the window's seconds,
    a tail over all its requests: one that failed or whose verdict
    differs counts as the window's length."""
    t0, t_end = w["t0"], w["t_end"][0]
    seconds = t_end - t0
    records = [r for cl in w["clients"] for r in cl.records]
    lost = sum(cl.unanswered for cl in w["clients"])
    differ = sum(1 for r in records if not r[2])
    in_window = sum(1 for r in records if r[2] and r[1] <= t_end)
    lat = [(r[1] - r[0]) * 1e3 if r[2] else seconds * 1e3 for r in records]
    lat += [seconds * 1e3] * lost
    if not lat:
        raise RunFailure("window", "no request was sent")
    return {
        "seconds": seconds, "attempted": len(lat), "failed": differ + lost,
        "differ": differ, "lost": lost,
        "values": {
            "verdicts_per_s": arith.rate(in_window, seconds),
            "latency_p50_ms": arith.percentile(lat, 50),
            "latency_p95_ms": arith.percentile(lat, 95),
        },
        "line": {
            "samples": len(lat), "bursts": sum(cl.bursts for cl in w["clients"]),
            "latency_p99_ms": arith.percentile(lat, 99), "latency_max_ms": max(lat),
            "answered_in_window": in_window, "window_s": seconds,
            # stalls show as a lean fifth
            "answered_by_fifth": [
                sum(1 for r in records if r[2] and k <= 5 * (r[1] - t0) / seconds < k + 1)
                for k in range(5)],
            "client_errors": [cl.error for cl in w["clients"] if cl.error],
        },
    }


def comparisons(cell: Cell, before: dict, after: dict, numbers: dict, on_tpu: bool,
                device_check: bool) -> list[tuple[str, int, int]]:
    """(name, value, limit) of every number ``correct`` rests on. The
    answers are exact (limit 0), and so is every counter but those of
    ``OFF_PATH``: their limit is ``OFF_PATH_SHARE`` of what the window
    sent or of the device windows it was served in, rounded down."""
    windows = dig(after, DEVICE_WINDOWS) - dig(before, DEVICE_WINDOWS)
    room = {"requests": int(OFF_PATH_SHARE * numbers["attempted"]),
            "windows": int(OFF_PATH_SHARE * windows)}
    out = [("verdicts_that_differ", numbers["differ"], 0),
           ("requests_unanswered", numbers["lost"], 0)]
    for key in cell.mix["zero_growth"]:
        out.append((f"growth.{key}", dig(after, key) - dig(before, key),
                    room.get(OFF_PATH.get(key), 0)))
    # An abandoned window's requests are counted as errors, not as requests.
    sent_through = after["batcher"]["requests"] - before["batcher"]["requests"]
    out.append(("batcher_requests_minus_attempted", abs(sent_through - numbers["attempted"]),
                room["requests"]))
    out.append(("not_promoted", int(after["serving_mode"] != "promoted"), 0))
    out.append(("instances_not_loaded", len(cell.not_loaded(after)), 0))
    out.append(("breaker_not_closed", int(after["degraded"]["breaker"]["state"] != "closed"), 0))
    if device_check:
        out.append(("not_on_tpu", int(not on_tpu), 0))
    return out


# -- the traced intervals ---------------------------------------------------------------


def capture_seconds(mix: dict, windows_per_s: float | None) -> float:
    """How long the first interval captures. A mix that states
    ``trace_windows`` captures that many device windows at the rate the
    run has just measured, and its ``trace_seconds`` is the cap; a mix
    that does not captures ``trace_seconds``. A first stop_trace costs by
    the window captured (PERF.md section 7), so a capture of fixed seconds
    costs the more the faster the program is."""
    cap = float(mix.get("trace_seconds", TRACE_SECONDS))
    windows = mix.get("trace_windows")
    if not windows or not windows_per_s or windows_per_s <= 0:
        return cap
    return min(cap, windows / windows_per_s)


def windows_read(sc: Sidecar) -> tuple[int, float]:
    """The device windows served so far, and when that was read."""
    n = dig(sc.stats(), DEVICE_WINDOWS)
    return n, time.perf_counter()


def traced_interval(sc: Sidecar, out_dir: Path, python_tracer: bool, capture_s: float,
                    t_window: float) -> dict:
    """One profiler interval under the load, and what it cost: the
    ``trace`` line. Nothing asks the sidecar anything between the two
    commands; the windows served are read before the start and after
    the stop, and the capture's share of them is by its share of that
    time (the sidecar serves on through a stop)."""
    n0, t0 = windows_read(sc)
    # The start answers in well under a poll: seen late, the capture would
    # run a fifth of a second over, a dozen windows of a fast cell.
    sc.command("trace_start", str(out_dir), "1" if python_tracer else "0", poll_s=0.02)
    t1 = time.perf_counter()
    time.sleep(capture_s)
    t2 = time.perf_counter()
    sc.command("trace_stop")
    t3 = time.perf_counter()
    n1, t4 = windows_read(sc)
    return {"phase": "trace", "ok": True, "python_tracer": python_tracer,
            "start_s": round(t1 - t0, 3), "capture_s": round(t2 - t1, 3),
            "stop_s": round(t3 - t2, 3),
            "windows": round((n1 - n0) * (t2 - t1) / (t4 - t0), 1),
            "since_window_start_s": round(time.perf_counter() - t_window, 3)}


# -- one run ----------------------------------------------------------------------


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_process_start: float,
    rehearse_cpu: bool = False,
    control: bool = False,
    launcher: str = "wafbench.sidecar_launch",
    device_check: bool = True,
) -> tuple[int, dict | None]:
    """Returns (exit code, result line or None). ``device_check=False``
    is for the tests under ``wafbench/tests`` alone: it leaves "ran on a
    TPU" out of ``correct``, so that a test on the CPU can see what else
    makes it false."""
    cell = Cell(workload)
    try:
        extra_args = cell.sidecar_args()  # refused before anything is started
    except RunFailure as f:
        emit({"phase": f.phase, "ok": False, "error": f.why, **f.detail})
        return 1, None
    # The cache server is JAX-free; importing it is also what fails in a
    # directory that holds the benchmark without the program.
    from coraza_kubernetes_operator_tpu.cache import RuleSetCache, RuleSetCacheServer

    held = os.environ.get("JAX_PLATFORMS", "")
    if held and "tpu" not in held.split(",") and not rehearse_cpu:
        emit({"phase": "device", "ok": False,
              "error": f"JAX_PLATFORMS={held} holds JAX off the chip"})
        return 1, None

    work = WORK / cell.workload["name"]
    control_dir, trace_dir, trace_py_dir = work / "control", work / "trace", work / "trace_py"
    for d in (control_dir, trace_dir, trace_py_dir):
        shutil.rmtree(d, ignore_errors=True)
    control_dir.mkdir(parents=True)
    cache_server = None
    sidecar: Sidecar | None = None
    try:
        # -- native library, from the committed source ----------------------------
        t0 = time.monotonic()
        lib = WORK / "libcko_native.so"
        build_log = WORK / "native_build.log"
        with open(build_log, "wb") as fh:
            rc = subprocess.call(["make", "-C", str(REPO / "native"), f"TARGET={lib}"],
                                 stdout=fh, stderr=subprocess.STDOUT)
        if rc != 0 or not lib.exists():
            raise RunFailure("native_build", f"make exited {rc}",
                             log=build_log.read_text(errors="replace").splitlines()[-20:])
        emit({"phase": "native_build", "ok": True, "seconds": round(time.monotonic() - t0, 3)})

        # -- rule sets into a cache server, traffic from the seed ------------------
        cache = RuleSetCache()
        cache_server = RuleSetCacheServer(cache, host="127.0.0.1", port=0)
        cache_server.start()
        for instance, text in cell.rules_texts(control=control).items():
            cache.put(instance, text)

        # -- the one chip-holding child: the sidecar as the configuration deploys it -
        env = dict(os.environ, CKO_NATIVE_LIB=str(lib))
        port = free_port()
        # A fixed path inside the checkout: the path is part of the key.
        argv = cell.sidecar_argv(
            cache_server.port, port,
            None if os.environ.get("JAX_COMPILATION_CACHE_DIR") else WORK / "jax_cache")
        log_path = work / "sidecar.log"
        t_child = time.monotonic()
        with open(log_path, "wb") as log_fh:
            proc = subprocess.Popen(
                [sys.executable, "-m", launcher, str(control_dir), "--", *argv],
                cwd=REPO, env=env, stdin=subprocess.PIPE, stdout=log_fh,
                stderr=subprocess.STDOUT,
            )
        sidecar = Sidecar(port, proc, log_path, control_dir)
        traffic = cell.traffic(seed)  # beside the sidecar's start-up, not before it

        sidecar.wait_for("ready", T_READY_S, "/waf/v1/readyz to answer 200",
                         lambda: sidecar.get("/waf/v1/readyz")[0] == 200)
        emit({"phase": "ready", "ok": True, "seconds": round(time.monotonic() - t_child, 3),
              "instances": [i["instance"] for i in cell.instances()],
              "sidecar_args": extra_args})

        def promoted():
            s = sidecar.stats()
            return (s["serving_mode"] == "promoted"
                    and s["compile_cache"]["inflight"] == 0) and s

        s = sidecar.wait_for("promotion", T_PROMOTE_S,
                             "serving_mode promoted with compile_cache.inflight 0", promoted)

        def instances_settled():
            # Loaded, or refused by the sidecar: ``instances_not_loaded``
            # then fails the run, and waiting longer would not load it.
            s = sidecar.stats()
            refused = lambda t: t.get("failed_reloads") or t.get("analyze_rejected")
            waiting = [k for k in cell.not_loaded(s)
                       if not refused(s["tenants"].get(k.strip("/"), {}))]
            return (not waiting and s["compile_cache"]["inflight"] == 0) and s

        s = sidecar.wait_for("instances", T_PROMOTE_S,
                             "every instance loaded with compile_cache.inflight 0",
                             instances_settled)
        device = s["device"]
        emit({"phase": "promotion", "ok": True,
              "seconds": round(time.monotonic() - t_child, 3), "device": device,
              "persistent_dir": s["compile_cache"]["persistent_dir"],
              "instances_not_loaded": cell.not_loaded(s)})
        on_tpu = bool(device) and device["platform"] == "tpu" \
            and device["count"] >= cell.workload["chips"]
        if not on_tpu and not rehearse_cpu:
            raise RunFailure("device", "the sidecar's first device window did not run on"
                             f" {cell.workload['chips']} TPU chip(s)", device=device)

        # -- warm: prime pass, then rounds of the timed loop until none mints ------
        t_prime = time.monotonic()
        n, bad = send_sequential(sidecar, traffic, traffic.prime, "prime")
        sidecar.settle("prime")
        emit({"phase": "prime", "ok": True, "requests": n, "differ": bad,
              "seconds": round(time.monotonic() - t_child, 3)})
        for i in range(WARM_ROUNDS_MAX):
            before = sidecar.stats()
            w = drive(sidecar, traffic, None)
            join(w, f"warm{i}", sidecar)
            after = sidecar.settle(f"warm{i}")
            minted = {k: dig(after, k) - dig(before, k) for k in MINTED}
            errors = [cl.error for cl in w["clients"] if cl.error]
            emit({"phase": f"warm{i}", "ok": not errors, "minted": minted, "errors": errors,
                  "seconds": round(time.monotonic() - t_child, 3)})
            if errors:
                raise RunFailure(f"warm{i}", "; ".join(errors), log=sidecar.tail())
            if not any(minted.values()):
                break
        else:
            raise RunFailure("warm", f"still compiling after {WARM_ROUNDS_MAX} warm rounds")

        # -- the measured window ------------------------------------------------------
        stats_setup = after
        before = sidecar.stats()
        gc.collect()
        gc.disable()  # no collector pause of this process lands in a latency
        w = drive(sidecar, traffic, float("inf") if trace else seconds)
        setup_s = time.monotonic() - t_process_start
        if trace:
            # The traced run reports no rate or tail, so its load simply
            # goes on until both intervals are on disk (writing a trace
            # can take longer than the window), and at least --seconds.
            time.sleep(seconds / 5)
            # The capture's length is settled here, from the windows the
            # load before it was served, and from nothing read later.
            led, t_led = windows_read(sidecar)
            rate = (led - dig(before, DEVICE_WINDOWS)) / (t_led - w["t0"])
            traced = [traced_interval(sidecar, trace_dir, False,
                                      capture_seconds(cell.mix, rate), w["t0"])]
            emit(dict(traced[0], windows_per_s=round(rate, 3)))
            # The Python tracer slows the host, so it gets an interval of
            # its own, read only for the names of what the host was doing.
            traced.append(traced_interval(
                sidecar, trace_py_dir, True,
                float(cell.mix.get("trace_python_seconds", TRACE_PY_SECONDS)), w["t0"]))
            emit(traced[1])
            w["t_end"][0] = max(w["t0"] + seconds, time.perf_counter())
        join(w, "window", sidecar)
        gc.enable()
        since_prime_s = time.monotonic() - t_prime
        after = sidecar.stats()
        memory = sidecar.command("memory")
        device = after["device"]

        # -- stop the sidecar: the drain must end in exit code 0 ---------------------
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=T_EXIT_S)
        except subprocess.TimeoutExpired:
            raise RunFailure("shutdown", f"sidecar still running {T_EXIT_S:.0f}s after SIGTERM",
                             log=sidecar.tail())
        if rc != 0:
            raise RunFailure("shutdown", f"sidecar exited {rc} on SIGTERM", log=sidecar.tail())
    except RunFailure as f:
        emit({"phase": f.phase, "ok": False, "error": f.why, **f.detail})
        return 1, None
    finally:
        gc.enable()
        if sidecar is not None and sidecar.proc.poll() is None:
            sidecar.proc.kill()
            sidecar.proc.wait()
        if cache_server is not None:
            cache_server.stop()

    try:
        numbers = window_numbers(w)
    except RunFailure as f:
        emit({"phase": f.phase, "ok": False, "error": f.why})
        return 1, None
    sched = after["scheduler"]
    emit({"phase": "window", **numbers["line"],
          # against the verdict cache's lifetime from insert, for a mix that repeats
          "since_prime_s": round(since_prime_s, 3),
          # where the program's adaptive scheduler stood, before and after
          "scheduler": {k: [before["scheduler"].get(k), sched.get(k)] for k in
                        ("lane_delay_ms", "pipeline_depth", "queue_budgets", "retunes_total")}})
    failed_checks = []
    compared = comparisons(cell, before, after, numbers, on_tpu, device_check)
    for name, value, limit in compared:
        ok = value <= limit
        if not ok:
            failed_checks.append(name)
        emit({"check": name, "value": value, "limit": limit, "ok": ok})
    attempted = numbers["attempted"]

    # -- the layers (traced run) ---------------------------------------------------------
    dev = {"platform": device["platform"], "kind": device["kind"], "count": device["count"],
           "memory_peak_bytes": memory["memory_peak_bytes"]}
    result = {"correct": not failed_checks, "attempted": attempted, "failed": numbers["failed"],
              "failed_checks": failed_checks}
    if not trace:
        values = dict(numbers["values"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics("end_to_end")}
    else:
        reduced_file = work / "trace_reduced.json"
        dirs = [str(trace_dir), str(trace_py_dir)]
        rc = subprocess.call(
            [sys.executable, "-m", "wafbench.trace_reduce", str(reduced_file), *dirs],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=sys.stderr,
        )
        if rc != 0:
            emit({"phase": "trace_reduce", "ok": False, "error": f"exited {rc}"})
            return 1, None
        reduced = json.loads(reduced_file.read_text())
        if not rehearse_cpu and not (reduced["device_plane"] and reduced["busy_s"] > 0):
            emit({"phase": "trace_reduce", "ok": False,
                  "error": "no operation ran on a device plane in the traced interval"})
            return 1, None
        ctx = {"before": before, "after": after, "setup": stats_setup, "attempted": attempted,
               "trace": reduced, "seconds": numbers["seconds"]}
        metrics = {}
        for m in cell.metrics("per_layer"):
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        # what the two intervals cost together, beside a run that failed in one
        result["trace_cost"] = {k: round(sum(t[k] for t in traced), 3)
                                for k in ("start_s", "capture_s", "stop_s", "windows")}
    result["metrics"] = metrics
    result["device"] = dev
    # Every number compared beside its limit: last in the line, and the
    # last lines of standard error.
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in compared}
    for name, value, limit in compared:
        print(f"compared {name} value={value} limit={limit}", file=sys.stderr)
    sys.stderr.flush()
    return 0, result
