"""The one traffic generator: turns a traffic file and a configuration
into ``SARequest``s and their due times, in wall seconds, from a seed.

A configuration (``bench/configs/<name>.json``) fixes the deployment's
request kinds: the cross product of its ``problems`` (objective, dim),
its cooling ``schedules`` and its ``slots_per_request``.  A traffic file
(``bench/traffic/<name>.json``) fixes how they arrive:

* ``"loop": "closed"``: ``clients`` callers, each of which sends its next
  request when it sees the result of its last one;
* ``"loop": "open"``: independent callers at ``rate`` requests per
  second, Poisson-like.

Every seed gets the same work in another order: the requests of a
window are the kinds repeated in a fixed order up to the count the
window needs, then permuted by the seed, and the open loop's gaps are
the exponential distribution's quantiles (mean ``1/rate``), permuted by
the seed.  The seed also sets each request's own seed (its initial
states and random streams), which changes no amount of work.
"""
from __future__ import annotations

import itertools
import math
from typing import Iterator, List

import numpy as np

from repro.service.request import SARequest


def request_kinds(config: dict) -> List[dict]:
    """Every request kind of the configuration, in a fixed order."""
    kinds = []
    for (objective, dim), sched, slots in itertools.product(
            config["problems"], config["schedules"],
            config["slots_per_request"]):
        kinds.append(dict(objective=objective, dim=int(dim), slots=int(slots),
                          **sched))
    return kinds


class Generator:
    """Requests of one run, numbered in the order they are handed out."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.kinds = request_kinds(config)
        self.cps = int(config["engine"]["chains_per_slot"])
        self.exchange = config.get("exchange", "sync")
        self._seq = np.random.SeedSequence(int(seed))
        self._rng = np.random.default_rng(self._seq)
        self._next_id = 0

    def _request_seed(self, req_id: int) -> int:
        return int(np.random.SeedSequence(
            [int(self._seq.entropy), req_id]).generate_state(1)[0])

    def make(self, kind: dict) -> SARequest:
        """The next request of ``kind``."""
        rid = self._next_id
        self._next_id += 1
        return SARequest(
            req_id=rid, objective=kind["objective"], dim=kind["dim"],
            n_chains=kind["slots"] * self.cps, T0=kind["T0"],
            T_min=kind["T_min"], rho=kind["rho"], N=kind["N"],
            seed=self._request_seed(rid), exchange=self.exchange)

    def kinds_for(self, n: int) -> List[dict]:
        """``n`` kinds: the fixed cycle up to ``n``, permuted by the seed."""
        cycle = [self.kinds[i % len(self.kinds)] for i in range(n)]
        return [cycle[i] for i in self._rng.permutation(n)]

    def closed_stream(self) -> Iterator[SARequest]:
        """Endless requests for a closed loop, in whole permuted cycles."""
        while True:
            for kind in self.kinds_for(len(self.kinds)):
                yield self.make(kind)

    def open_schedule(self, seconds: float) -> Iterator[tuple]:
        """Endless ``(due offset in s, request)`` for an open loop.

        Each stretch of ``seconds`` holds ``round(rate * seconds)``
        arrivals whose gaps sum to ``seconds`` exactly, so the first
        stretch (the measured window) offers the same count every seed.
        """
        rate = float(self.traffic["rate"])
        n = max(1, round(rate * seconds))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q)
        gaps *= seconds / gaps.sum()
        start = 0.0
        while True:
            t = start
            for gap, kind in zip(gaps[self._rng.permutation(n)],
                                 self.kinds_for(n)):
                yield t, self.make(kind)
                t += float(gap)
            start += seconds


def warmup_requests(config: dict, clients: float = math.inf) -> List[tuple]:
    """One-level requests that make the engine compile every program the
    traffic can use: for each (dim, N) and each power-of-two block count
    a group of that (dim, N) can pad to, ``(slots, request)``.

    A group holds the slots of the resident requests of one (dim, N), at
    most the pool's ``n_slots``, and with a closed loop at most
    ``clients`` requests.
    """
    eng = config["engine"]
    n_slots, cps = int(eng["n_slots"]), int(eng["chains_per_slot"])
    out = []
    by_shape = {}           # (dim, N) -> (an objective of it, footprints)
    for kind in request_kinds(config):
        _, sizes = by_shape.setdefault((kind["dim"], kind["N"]),
                                       (kind["objective"], set()))
        sizes.add(kind["slots"])
    for (dim, N), (objective, sizes) in sorted(by_shape.items()):
        cap = min(n_slots, max(sizes) * clients)
        reach = {0}
        while True:          # every sum of request footprints up to cap
            more = {r + s for r in reach for s in sizes if r + s <= cap}
            if more <= reach:
                break
            reach |= more
        padded = sorted({1 << (r - 1).bit_length() for r in reach if r})
        for p in padded:     # T0=1, T_min=0.5, rho=0.5: one level
            out.append((p, SARequest(
                req_id=-1, objective=objective, dim=dim, n_chains=p * cps,
                T0=1.0, T_min=0.5, rho=0.5, N=N)))
    return out
