"""The one traffic generator: a traffic file's parameters and a seed in,
requests and the sources that feed them to the engine out.

The length arithmetic follows the program's ``engine/loadgen/workload.py``
(seeded lognormal lengths, clipped), copied here so that no change to the
program moves the yardstick. One property differs on purpose: the sizes
(stratified quantiles of the file's distributions) are the same for
every seed and come in the same order; the seed draws the prompt tokens.
A window serves only the head of the queue, so the order of the sizes
decides which prefill buckets it meets and when slots free: drawn from
the seed, it moved ``output_tok_s`` by ~4% between seeds where one seed
read twice moved it by 0.2% (starcoder2-3b on a TPU v5e). Runs with
different seeds now do the same work, and their spread is the system's,
not the draw's.

The one loop, ``backlog``, is a batch job already running: the window
opens on a job in its steady state, not on its first fill. Every slot
holds a request part-way through its output (the first fill), and a
queue of fresh requests waits behind them. A first-fill request stands
for one that has already produced ``done`` of its ``budget`` tokens: its
prompt is its own prompt followed by ``done`` tokens, and ``budget -
done`` tokens are left to serve. Its budget is drawn from the
length-biased output distribution (a slot is more often held by a long
request than by a short one) and ``done / budget`` is spread evenly over
(0, 1), so requests finish, and slots refill, from the first step on.
The first fill is the same (prompt, budget, done) for every seed; each
block of ``block`` queued requests holds the same stratified sizes, in
an order fixed apart from the seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    prompt: np.ndarray
    max_new: int
    first_fill: bool
    arrival_s: Optional[float] = None     # None: arrived when submitted


def quantiles(dist: Dict, n: int, biased: bool = False) -> np.ndarray:
    """n stratified quantiles ((i + 0.5) / n) of a length distribution
    {"dist": "lognormal", "median", "sigma", "min", "max"}, rounded and
    clipped to [min, max]. ``biased``: of the length-biased distribution
    (each length weighted by itself), read off a fine grid."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")

    def plain(m):
        z = np.array([NormalDist().inv_cdf((i + 0.5) / m) for i in range(m)])
        x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
        return np.clip(x, dist["min"], dist["max"]).astype(np.int64)
    if not biased:
        return plain(n)
    grid = plain(4096)
    cdf = np.cumsum(grid) / grid.sum()
    return grid[np.searchsorted(cdf, (np.arange(n) + 0.5) / n)]


def first_fill(traffic: Dict) -> List[Dict[str, int]]:
    """The (prompt, budget, done) of every slot when the window opens:
    the same for every seed. Prompts and budgets are paired with the
    evenly spread progress by fixed permutations."""
    n = int(traffic["slots"])
    prompts = quantiles(traffic["prompt"], n)
    budgets = quantiles(traffic["output"], n, biased=True)
    fixed = np.random.default_rng(0)
    prompts, budgets = fixed.permutation(prompts), fixed.permutation(budgets)
    return [{"prompt": int(p), "budget": int(b),
             "done": int((i + 0.5) / n * b)}
            for i, (p, b) in enumerate(zip(prompts, budgets))]


def generate(traffic: Dict, seed: int, vocab: int) -> List[Request]:
    """The requests of one run, in queue order: the first fill, then the
    queued requests. Only the prompt tokens depend on ``seed``."""
    if traffic["loop"] != "backlog":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    rng = np.random.default_rng(seed)
    out = [Request(i, rng.integers(0, vocab, f["prompt"] + f["done"])
                   .astype(np.int32), f["budget"] - f["done"], True)
           for i, f in enumerate(first_fill(traffic))]
    block, n = int(traffic["block"]), int(traffic["queued"])
    reps = -(-n // block)
    fixed = np.random.default_rng(1)
    plens = np.concatenate([fixed.permutation(quantiles(
        traffic["prompt"], block)) for _ in range(reps)])[:n]
    budgets = np.concatenate([fixed.permutation(quantiles(
        traffic["output"], block)) for _ in range(reps)])[:n]
    out += [Request(len(out) + i,
                    rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                    int(budgets[i]), False) for i in range(n)]
    return out


class _Source:
    """What ``InferenceEngine.run(source=...)`` polls at every scheduling
    boundary. Stopping raises ``KeyboardInterrupt`` from :meth:`due`,
    which the engine answers with its graceful shutdown: in-flight
    requests keep the tokens they have, queued ones are dropped."""

    exhausted = False              # only a stop in ``due`` ends the run

    def on_finish(self, now_s: float) -> None:
        pass

    def next_at(self) -> Optional[float]:
        return None


class Backlog(_Source):
    """The window's source. The first poll submits every request; the
    window opens when the first fill has its first tokens
    (``started()`` returns that time, on ``clock``'s scale, or None
    before), and the engine is stopped at the first boundary at least
    ``seconds`` later. There nothing more is sent: ``on_stop`` runs (it
    waits for the work already sent), and the window closes at
    ``stopped_at``, read after it."""

    def __init__(self, requests: List[Request], seconds: float,
                 started: Callable[[], Optional[float]],
                 clock: Callable[[], float],
                 on_start: Callable[[], None] = lambda: None,
                 on_stop: Callable[[], None] = lambda: None):
        self.requests = requests
        self.seconds = seconds
        self._started, self._clock, self._on_start = started, clock, on_start
        self._on_stop = on_stop
        self.submitted: List[Request] = []
        self.start: Optional[float] = None
        self.stopped_at: Optional[float] = None

    def due(self, now_s: float):
        if not self.submitted:
            self.submitted = list(self.requests)
            return self.submitted
        if self.start is None:
            self.start = self._started()
            if self.start is None:
                return []
            self._on_start()
        if self._clock() >= self.start + self.seconds:
            self._on_stop()
            self.stopped_at = self._clock()
            raise KeyboardInterrupt
        return []


class Script(_Source):
    """A warm-up source: one group of requests per poll, then a stop once
    every request of ``max_new`` 2 or less has finished (longer ones
    only hold pages, so that the decode steps run at the page count
    they reserve)."""

    def __init__(self, groups: List[List[Request]]):
        self.groups = groups
        self._i = 0
        self._short = sum(r.max_new <= 2 for g in groups for r in g)
        self._finished = 0

    def due(self, now_s: float):
        if self._i < len(self.groups):
            self._i += 1
            return self.groups[self._i - 1]
        if self._finished >= self._short:
            raise KeyboardInterrupt
        return []

    def on_finish(self, now_s: float) -> None:
        self._finished += 1
