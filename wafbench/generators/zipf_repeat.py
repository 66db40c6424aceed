"""Repeat traffic: planned bursts in which most requests are unsalted
draws from a small fixed pool, Zipf-distributed, and a few are salted.

The configuration's pool (``corpus.jsonl``) and ``planned_bursts``'s
salting are used as they are. A plan (``plans/<plan>.json``) names, per
lane, the ``repeat`` pool (pool requests sent with one fixed salt each,
so byte for byte the same on every send: what a verdict cache and
in-window dedup answer) and the ``steady`` groups of requests that get
a fresh salt on every send (what still rides a device window). A burst
is one steady group with ``repeat_per_burst`` draws from the lane's
repeat pool around it, rank ``k`` drawn with weight ``k ** -zipf_s``.
From ``--seed`` come the order of the groups, the draws, where in the
burst the salted requests sit, and the salts. The prime pass sends each
lane's repeat pool once, whole, so that every later draw is a repeat;
where the plan has a ``prime`` list it sends those groups instead, one
burst each (a whole pool in one burst is one window of whatever shape
its rows make, and a large rule set pays a compile for every shape).
Every request of a prime group goes out on its fixed salt, so a plan's
groups must hold the repeat pools, and may hold more: requests that
only the steady groups send, seen once so that their unsalted values
are cached before a steady group first rides a window.
"""

from __future__ import annotations

import base64
import itertools
import json
import random
from pathlib import Path

from wafbench.generators.planned_bursts import SALT_TOKEN, salt_for


class Burst:
    """Requests in send order: ``parts`` split at the salt (one part:
    nothing to salt), and the reference verdict of each."""

    __slots__ = ("lane", "parts", "expected", "n")

    def __init__(self, lane: str, requests: list[tuple[list[bytes], tuple]]):
        self.lane = lane
        self.parts = [p for p, _ in requests]
        self.expected = [e for _, e in requests]
        self.n = len(requests)


class Traffic:
    def __init__(self, config_dir: Path, mix: dict, seed: int):
        self.seed = seed
        self.salt_hex = int(mix["salt_hex"])
        self.draws = int(mix["repeat_per_burst"])
        pool = []
        with open(config_dir / "corpus.jsonl") as fh:
            for line in fh:
                r = json.loads(line)
                pool.append((base64.b64decode(r["wire"]), (r["status"], r["rule_id"])))
        plan = json.loads((config_dir / "plans" / f"{mix['plan']}.json").read_text())

        def fixed(i: int):  # a repeat request: the same salt on every send
            salt = salt_for(0, "repeat", i, self.salt_hex)
            return [pool[i][0].replace(SALT_TOKEN, salt)], pool[i][1]

        def fresh(i: int):
            return pool[i][0].split(SALT_TOKEN), pool[i][1]

        self.repeat = {lane: [fixed(i) for i in idxs] for lane, idxs in plan["repeat"].items()}
        self.cum = {lane: list(itertools.accumulate(
            (k + 1) ** -float(mix["zipf_s"]) for k in range(len(reqs))))
            for lane, reqs in self.repeat.items()}
        if "prime" in plan:
            self.prime = [Burst(g["lane"], [fixed(i) for i in g["requests"]])
                          for g in plan["prime"]]
        else:
            self.prime = [Burst(lane, reqs) for lane, reqs in self.repeat.items()]
        self.connections = []
        for c in mix["connections"]:
            mine = [(g["lane"], [fresh(i) for i in g["requests"]])
                    for g in plan["steady"] if g["lane"] in c["lanes"]]
            if not mine:
                raise ValueError(f"no planned burst for lanes {c['lanes']}")
            self.connections.append(mine)
        self._serial: dict[str, int] = {}

    def salted(self, burst: Burst, stream: str) -> bytes:
        """Wire bytes of ``burst``; every request that has a salt gets
        one no earlier send of ``stream`` has had."""
        first = self._serial.get(stream, 0)
        self._serial[stream] = first + burst.n
        return b"".join(
            parts[0] if len(parts) == 1
            else salt_for(self.seed, stream, first + k, self.salt_hex).join(parts)
            for k, parts in enumerate(burst.parts))

    def stream(self, conn: int):
        """Connection ``conn``'s groups in a seeded order, then again in
        another, without end; each with its draws around it."""
        rng = random.Random(f"{self.seed}/{conn}")
        mine = self.connections[conn]
        while True:
            order = list(range(len(mine)))
            rng.shuffle(order)
            for i in order:
                lane, group = mine[i]
                requests = rng.choices(self.repeat[lane], cum_weights=self.cum[lane],
                                       k=self.draws)
                for r in group:
                    requests.insert(rng.randrange(len(requests) + 1), r)
                yield Burst(lane, requests)
