"""Shared engine-test harness (tests only, not part of the package).

Consolidates the poll-scripted-arrival source and the prompt builders
that the resilience, prefix-cache and chunked-prefill suites all need:
deterministic mid-run arrivals let a test inject a request at an exact
scheduling boundary (e.g. while a victim is decoding, or mid-chunk), so
preemption paths replay bit-identically without wall-clock sleeps.
"""
import contextlib

import numpy as np

from repro.engine import engine as engine_mod
from repro.engine.loadgen import ArrivalSource, GeneratedRequest


@contextlib.contextmanager
def engine_kernels(on: bool):
    """Test-only seam: engines built inside the block run their steps
    through the Pallas kernels (``on``; interpret mode on the CPU) or the
    jnp references, whatever the platform would choose."""
    saved = engine_mod.kernels_on
    engine_mod.kernels_on = lambda: on
    try:
        yield
    finally:
        engine_mod.kernels_on = saved


class ScriptedSource(ArrivalSource):
    """Poll-count-scheduled arrivals: request i is delivered at the
    engine's N-th poll of the source, independent of wall clock — the
    engine polls once per scheduling boundary, so mid-run arrivals land
    at deterministic boundaries and preemption tests replay exactly."""

    def __init__(self, schedule):
        # schedule: [(poll_index, prompt, max_new, priority), ...]
        self._sched = sorted(schedule, key=lambda s: s[0])
        self._polls = 0
        self._i = 0

    def due(self, now_s):
        self._polls += 1
        out = []
        while (self._i < len(self._sched)
               and self._sched[self._i][0] <= self._polls):
            _, prompt, max_new, prio = self._sched[self._i]
            out.append(GeneratedRequest(
                idx=self._i, arrival_s=None, think_s=None,
                prompt=prompt, max_new=max_new, priority=prio))
            self._i += 1
        return out

    def next_at(self):
        return None

    @property
    def exhausted(self):
        return self._i >= len(self._sched)


def make_prompts(vocab, lens, seed=0):
    """Random prompts of the given lengths (one seeded stream)."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=l).astype(np.int32) for l in lens]


def shared_prompts(vocab, prefix_len, tail_lens, seed=0):
    """Prompts sharing one random prefix, with random tails of the given
    lengths (0 = the bare prefix: the page-aligned COW case)."""
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, vocab, size=prefix_len).astype(np.int32)
    return [np.concatenate([pre, rng.integers(0, vocab, size=n)
                            .astype(np.int32)]) for n in tail_lens]


def by_rid(res):
    """{rid: [tokens]} from an engine run() result dict."""
    return {r["rid"]: list(r["tokens"]) for r in res["results"]}
