"""The general traffic generator: planned bursts over a frozen request pool.

A configuration holds a pool of requests (``corpus.jsonl``: wire bytes
with ``SALT_TOKEN`` where a per-send salt goes, and the reference
verdict) and, per plan, a list of bursts (``plans/<plan>.json``: which
pool requests go down one connection in one write). A mix
(``traffic/<mix>.json``) names the plan, the salt length and the
connections, each with the lanes whose bursts it sends. From ``--seed``
come only the order of the bursts and the salts, so every seed sends
the same set of bursts, hence the same sizes and window shapes, in
another order, and no two requests ever sent are equal.
"""

from __future__ import annotations

import base64
import hashlib
import json
import random
from pathlib import Path

SALT_TOKEN = b"__WAFBENCH_SALT__"


def salt_for(seed: int, stream: str, serial: int, n_hex: int) -> bytes:
    return hashlib.shake_256(f"{seed}/{stream}/{serial}".encode()).hexdigest(n_hex // 2).encode()


class Burst:
    """One planned burst: request templates split at the salt, and the
    reference verdict of each request."""

    __slots__ = ("lane", "parts", "expected", "n")

    def __init__(self, lane: str, templates: list[bytes], expected: list[tuple]):
        self.lane = lane
        self.parts = [t.split(SALT_TOKEN) for t in templates]
        self.expected = expected
        self.n = len(templates)

    def wire(self, seed: int, stream: str, first_serial: int, salt_hex: int) -> bytes:
        out = []
        for k, parts in enumerate(self.parts):
            out.append(salt_for(seed, stream, first_serial + k, salt_hex).join(parts))
        return b"".join(out)


class Traffic:
    """What a run sends: the prime pass, and per connection an endless
    seeded stream of steady bursts."""

    def __init__(self, config_dir: Path, mix: dict, seed: int):
        self.seed = seed
        self.salt_hex = int(mix["salt_hex"])
        pool = []
        with open(config_dir / "corpus.jsonl") as fh:
            for line in fh:
                r = json.loads(line)
                pool.append((base64.b64decode(r["wire"]), (r["status"], r["rule_id"])))
        plan = json.loads((config_dir / "plans" / f"{mix['plan']}.json").read_text())

        def burst(b: dict) -> Burst:
            return Burst(b["lane"], [pool[i][0] for i in b["requests"]],
                         [pool[i][1] for i in b["requests"]])

        self.prime = [burst(b) for b in plan["prime"]]
        self.steady = [burst(b) for b in plan["steady"]]
        self.connections = []
        for c in mix["connections"]:
            mine = [b for b in self.steady if b.lane in c["lanes"]]
            if not mine:
                raise ValueError(f"no planned burst for lanes {c['lanes']}")
            self.connections.append(mine)
        self._serial: dict[str, int] = {}

    def salted(self, burst: Burst, stream: str) -> bytes:
        """Wire bytes of ``burst`` with salts no earlier send has had.
        ``stream`` names the sender (one thread each), so that senders
        share no counter."""
        first = self._serial.get(stream, 0)
        self._serial[stream] = first + burst.n
        return burst.wire(self.seed, stream, first, self.salt_hex)

    def stream(self, conn: int):
        """Connection ``conn``'s bursts: its set in a seeded order, then
        again in another seeded order, without end."""
        rng = random.Random(f"{self.seed}/{conn}")
        mine = self.connections[conn]
        while True:
            order = list(range(len(mine)))
            rng.shuffle(order)
            for i in order:
                yield mine[i]
