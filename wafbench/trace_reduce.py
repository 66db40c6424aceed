"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy seconds, the operations and executables
that took them, and the idle gaps with what the host was doing in each.

Two stages, so that the arithmetic can be checked on a small recorded
trace without JAX:

``extract(path)``  reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
                   into plain lists (run in a CPU-pinned child: the parent
                   of a benchmark run never imports JAX);
``reduce(events)`` is plain Python over those lists.

    JAX_PLATFORMS=cpu python -m wafbench.trace_reduce <out.json> <trace dir or file> [<second trace>]

The numbers come from the first trace. Where a second is given (taken
with the Python tracer on, which slows the host and so is kept out of
the numbers), only the names of the idle gaps come from it.

On a TPU the device planes are ``/device:TPU:<n>``; their line ``XLA
Ops`` holds one event per executed HLO operation and ``XLA Modules`` one
per executable run. A CPU rehearsal has no device plane: there the
events that carry an ``hlo_op`` stat on the host's XLA threads stand in,
and the result says so (``device_plane: false``).
"""

from __future__ import annotations

import bisect
import json
import sys
from pathlib import Path

TOP = 10
NAME_MAX = 120
MIN_HOST_EVENT_NS = 20_000  # host events shorter than this explain no gap worth listing
# A thread inside one of these is waiting, not working: it explains no gap.
BLOCKING = {"wait", "acquire", "get", "poll", "select", "recv", "recv_into", "sleep", "accept",
            "read", "readinto", "join", "result", "run_forever", "_run_once", "epoll_wait",
            "start_trace", "stop_trace"}  # the last two: the benchmark's own control thread


def op_name(text: str) -> str:
    """The TPU trace names an operation by its whole HLO text
    (``%fusion.3 = f32[...] fusion(...)``): keep the name."""
    return text.split(" = ", 1)[0].lstrip("%")[:NAME_MAX]


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def extract(path: Path) -> dict:
    """Planes -> plain lists. Times in nanoseconds as the trace has them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(path)))
    devices, host, stand_in = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [[op_name(e.name), e.start_ns, e.duration_ns]
                                  for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                thread = f"{line.name.split('/')[0]}#{k}"  # names repeat; lines do not
                for e in line.events:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        stand_in.append([f"{stats.get('hlo_module', '?')}/{e.name}",
                                         e.start_ns, e.duration_ns])
                    elif e.duration_ns >= MIN_HOST_EVENT_NS:
                        host.append([thread, e.name[:NAME_MAX], e.start_ns, e.duration_ns])
    if not devices and stand_in:
        devices = [{"name": "host-stand-in", "ops": stand_in, "modules": [], "stand_in": True}]
    return {"devices": devices, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(events: list[list]) -> dict[str, float]:
    """Seconds by name, each event less what its direct children cover
    (a ``while`` spans its body's operations on the same line)."""
    total: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def pop() -> None:
        name, _end, self_ns = stack.pop()
        total[name] = total.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            pop()
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        pop()
    return total


def _top(d: dict[str, float]) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce(events: dict) -> dict:
    """The numbers. ``busy_s`` is the union of device-operation intervals,
    averaged over the device planes; ``window_s`` spans every event."""
    devices, host = events["devices"], events["host"]
    spans = [(s, s + d) for dev in devices for _n, s, d in dev["ops"] + dev["modules"]]
    spans += [(s, s + d) for _t, _n, s, d in host]
    if not spans:
        return {"device_plane": False, "window_s": 0.0, "busy_s": 0.0, "device_ops": [],
                "idle_gaps": [], "module_busy_s": {}, "module_runs": {}, "module_events": [],
                "devices": 0}
    t0, t1 = min(a for a, _ in spans), max(b for _, b in spans)
    busy_ns, ops, modules, runs, gaps, module_events = 0.0, {}, {}, {}, {}, []
    starts: dict[str, list] = {}  # per host thread, when its events began
    for thread, _n, s, _d in host:
        starts.setdefault(thread, []).append(s)
    for began in starts.values():
        began.sort()
    for dev in devices:
        merged = _union([(s, s + d) for _n, s, d in dev["ops"]])
        busy_ns += sum(b - a for a, b in merged)
        for name, sec in _self_times(dev["ops"]).items():
            ops[name] = ops.get(name, 0.0) + sec
        for name, _s, d in dev["modules"]:
            modules[name] = modules.get(name, 0.0) + d / 1e9
            runs[name] = runs.get(name, 0) + 1
        module_events.append([[name, (s - t0) / 1e9, d / 1e9]
                              for name, s, d in sorted(dev["modules"], key=lambda e: e[1])])
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        idle = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)), reverse=True)[: 4 * TOP]
        for length, a, b in idle:
            if length <= 0:
                continue
            # The tightest event over at least half of the gap in which its
            # thread went on working (it began further events inside the
            # gap: a thread that only waits for the interpreter lock sits
            # in one event, however small the function); failing that the
            # tightest at all; failing that the one that covers most.
            best, best_key = "every traced host thread was waiting", (0, 0.0)
            for thread, name, s, d in host:
                overlap = min(b, s + d) - max(a, s)
                if overlap <= 0 or name.rsplit(" ", 1)[-1] in BLOCKING:
                    continue
                if 2 * overlap >= length:
                    began = starts[thread]
                    working = bisect.bisect_left(began, min(b, s + d)) \
                        - bisect.bisect_right(began, max(a, s))
                    key = (2 if working else 1, -d)
                else:
                    key = (0, overlap)
                if key > best_key:
                    best, best_key = f"{thread.split('#')[0]}:{name}", key
            gaps[best] = gaps.get(best, 0.0) + length / 1e9
    n = max(len(devices), 1)
    return {
        "device_plane": bool(devices) and not any(d.get("stand_in") for d in devices),
        "devices": len(devices),
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "device_ops": _top(ops),
        "idle_gaps": _top(gaps),
        "module_busy_s": modules,
        "module_runs": runs,
        # per device plane, every executable run in the order it ran: [name, start_s, seconds]
        "module_events": module_events,
    }


def main(argv: list[str]) -> int:
    out = reduce(extract(Path(argv[1])))
    if len(argv) > 2:
        named = reduce(extract(Path(argv[2])))
        out["idle_gaps"], out["idle_gaps_from"] = named["idle_gaps"], "python-traced interval"
    Path(argv[0]).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
