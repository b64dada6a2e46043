"""The continuous-batching inference engine (DESIGN.md §3).

One jitted *batched prefill* runs each admission group's full prompts
through flash attention and scatters their K/V into the paged cache; one
jitted *fused decode step* advances every slot at its own position and
samples the next token on device. The sampled token array is fed straight
back into the next decode call (device-side token feedback) — the host
never pulls tokens mid-flight. Because stopping is purely budget-based,
host control flow needs no per-step sync: the loop dispatches a whole
decode *segment* (until the earliest active request exhausts its budget)
and blocks once at the segment boundary, which is also where timestamps
are taken and slots are evicted/refilled.

With ``spec_k > 0`` the segment interleaves draft/verify *rounds*
instead of single-token steps (self-speculative decoding, DESIGN.md §4):
a fused K-step greedy draft call with the aggressively-compressed draft
parameter set, then one multi-token verify call that emits 1..K+1 tokens
per slot. Budgets are clamped on device, so segments stay sync-free.
``spec_fanout`` upgrades the round to a token TREE (DESIGN.md §8):
top-k branches per draft depth, one T = N+1 tree-attention verify, and
an accepted-path KV compaction — optionally retuned online per segment
from the observed acceptance rate (``spec_adaptive``).

With ``prefill_chunk_tokens > 0`` prefill stops being atomic
(Sarathi-style chunked prefill, DESIGN.md §14): an admitted prompt
whose unshared tail exceeds the budget enters a ``PREFILLING`` state
and feeds one token-budget chunk per scheduling boundary through the
ragged ``tail_fn`` path — a chunk is just a tail whose shared boundary
is the previous chunk's end — while the other slots keep decoding
(segments clamp to one step so chunks interleave at token granularity).
The decode loop itself runs *two-deep*: each segment's boundary sync
waits on the PREVIOUS segment's tokens (a trailing copy), so the host
schedules segment N+1 while N still executes and issues strictly fewer
``block_until_ready`` calls than segments dispatched. A request whose
budget runs out is evicted (and its slot refilled) at the boundary where
its last segment is dispatched, but its finish is stamped when the host
sees that segment's tokens ready: at the sync that retires it, or at the
drain when ``run`` ends.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.kv_cache import PagedKVCache
from repro.engine.metrics import EngineMetrics
from repro.engine.resilience import (ChaosDeviceError, PRESSURE_CRITICAL,
                                     PRESSURE_ELEVATED, ResilienceConfig,
                                     choose_victims, make_injector,
                                     pressure_level)
from repro.engine.sampling import SamplingParams, sample
from repro.engine.scheduler import DECODE, PREFILLING, Request, Scheduler
from repro.engine.telemetry import Telemetry
from repro.models.moe import expert_rows
from repro.models.registry import get_model


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 4
    max_seq: int = 64                 # per-request prompt + budget cap
    page_size: int = 16
    num_pages: Optional[int] = None   # None: num_slots * max_seq / page_size
    prompt_bucket_min: int = 8        # prefill pad bucket floor (pow2 above)
    seed: int = 0
    # shared-prefix KV reuse (engine/prefix_cache.py, DESIGN.md §13):
    # admission maps cached full-page prompt blocks to existing pages
    # (refcounted, copy-on-write) and prefills only the unshared tail.
    # Greedy outputs are bit-identical on/off (pinned by test).
    prefix_cache: bool = False
    # overload resilience (engine/resilience/, DESIGN.md §12): preemption
    # + shedding + pressure degrade + optional chaos injection. None uses
    # the all-defaults ResilienceConfig (inert without priority
    # inversions, deadlines or a chaos spec).
    resilience: Optional[ResilienceConfig] = None
    # speculative decoding: draft K tokens per round with the (separately
    # compressed) draft parameter set, verify all K in one multi-token
    # target step. 0 disables; > 0 requires draft_params at engine
    # construction (engine/spec/, DESIGN.md §4). spec_draft_layers: the
    # drafter's depth for depth-pruned draft profiles (None = full depth;
    # must match core.model_compress.draft_layers of the profile used).
    spec_k: int = 0
    spec_draft_layers: Optional[int] = None
    # token-TREE drafting (engine/spec/tree.py, DESIGN.md §8): fanout per
    # draft depth, e.g. (4, 2, 2) = 28 nodes / 16 leaves / depth 3 — the
    # round's verify block is all N+1 tree slots and 1..depth+1 tokens
    # emerge per slot. Overrides spec_k (which stays the CHAIN path).
    spec_fanout: Optional[Tuple[int, ...]] = None
    # retune the tree online from a per-slot EWMA of the observed
    # acceptance rate: thrash shrinks to a chain K=1, sustained
    # acceptance widens back to the full spec_fanout profile
    spec_adaptive: bool = False
    # chunked prefill (DESIGN.md §14): split each admitted prompt into
    # chunks of at most this many tokens and interleave them into the
    # decode loop (one chunk per scheduling boundary) instead of one
    # monolithic admission prefill — bounds the TPOT jitter prefills
    # inject into co-resident decodes. 0 = monolithic (the historical
    # behaviour); greedy outputs are bit-identical on/off (pinned).
    prefill_chunk_tokens: int = 0


def kernels_on() -> bool:
    """The platform picks the step path: the Pallas kernels on a TPU, the
    plain-jnp references (`kernels/ref.py`) on the CPU. There is no user
    option; tests replace this function to drive the interpret-mode
    kernels through the engine."""
    return jax.default_backend() == "tpu"


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def plan_chunks(start: int, prompt_len: int,
                budget: int) -> List[Tuple[int, int]]:
    """Chunk planner (DESIGN.md §14): split prompt positions
    [start, prompt_len) into ``(chunk_start, chunk_len)`` pieces of at
    most ``budget`` tokens, covering every position exactly once. The
    last chunk always ends exactly at ``prompt_len`` — its sampled
    token is the request's first output token, so the final chunk is
    never empty. ``budget <= 0`` means monolithic: one chunk."""
    if budget <= 0:
        return [(start, prompt_len - start)]
    out = []
    p = start
    while p < prompt_len:
        n = min(budget, prompt_len - p)
        out.append((p, n))
        p += n
    return out


@functools.lru_cache(maxsize=32)
def _step_fns(cfg, sampling: SamplingParams, use_pallas: bool):
    """Jitted prefill/decode steps, shared across engine instances with the
    same (model config, sampling, backend) — a fresh engine per workload
    must not recompile (both keys are frozen dataclasses)."""
    api = get_model(cfg)

    # jax.named_scope: trace-time-only phase names so device profiler
    # traces line up with the host spans (telemetry, DESIGN.md §10) —
    # no runtime cost once compiled
    def prefill_fn(params, cache, tokens, lengths, block_tables, rng):
        with jax.named_scope("engine_prefill"):
            logits, cache = api.prefill(params, cache, tokens, lengths,
                                        block_tables, cfg, None, use_pallas)
            rng, sub = jax.random.split(rng)
            first = sample(logits[:, -1, :], sub, sampling)
        return first, cache, rng

    def decode_fn(params, cache, tokens, positions, block_tables,
                  active, rng, max_live):
        with jax.named_scope("engine_decode"):
            logits, cache = api.decode_step(params, cache, tokens[:, None],
                                            positions, cfg, None, use_pallas,
                                            block_tables=block_tables,
                                            max_live_pages=max_live)
            rng, sub = jax.random.split(rng)
            with jax.named_scope("engine_sample"):
                nxt = sample(logits[:, -1, :], sub, sampling)
        return nxt, positions + active, cache, rng

    def tail_fn(params, cache, tokens, positions, feed_len, block_tables,
                rng, max_live):
        # prefix-cache tail prefill (DESIGN.md §13): slots whose prompt
        # prefix is served from cached pages feed only the unshared tail
        # — a ragged multi-token decode block (token t writes/attends at
        # positions + t, rows padded to one T and sentinel-masked past
        # feed_len). First-token logits come from each row's LAST real
        # token, so the clamp in assign guarantees feed_len >= 1.
        with jax.named_scope("engine_prefill_tail"):
            logits, cache = api.decode_step(params, cache, tokens,
                                            positions, cfg, None, use_pallas,
                                            block_tables=block_tables,
                                            max_live_pages=max_live,
                                            feed_len=feed_len)
            last = jnp.take_along_axis(
                logits, jnp.maximum(feed_len - 1, 0)[:, None, None],
                axis=1)[:, 0, :]
            rng, sub = jax.random.split(rng)
            first = sample(last, sub, sampling)
        return first, cache, rng

    # max_live is static: it clamps the block tables to the batch's max
    # occupied page count (pow2-bucketed by the engine, so at most
    # log2(max_pages_per_slot) retraces per engine lifetime)
    return (jax.jit(prefill_fn), jax.jit(decode_fn, static_argnums=(7,)),
            jax.jit(tail_fn, static_argnums=(7,)))


class InferenceEngine:
    # adaptive tree control (spec_adaptive): per-slot EWMA of the round
    # acceptance fraction; below LOW the segment falls back to a chain
    # K=1, at/above HIGH it runs the full spec_fanout profile, between
    # them a depth-equal chain (cheap drafts, no width)
    SPEC_EWMA_INIT = 0.5
    SPEC_EWMA_BETA = 0.7
    SPEC_EWMA_LOW = 0.35
    SPEC_EWMA_HIGH = 0.65

    def __init__(self, cfg, params, engine_cfg: EngineConfig = EngineConfig(),
                 sampling: SamplingParams = SamplingParams(),
                 draft_params=None, telemetry: Optional[Telemetry] = None):
        api = get_model(cfg)
        if not api.supports_paged_cache:
            from repro.models.registry import paged_families
            raise NotImplementedError(
                f"family {cfg.family!r} lacks prefill/paged-cache support "
                f"(supported: {', '.join(paged_families())})")
        self._spec_tree = engine_cfg.spec_fanout is not None
        spec = engine_cfg.spec_k > 0 or self._spec_tree
        if spec and draft_params is None:
            raise ValueError("speculative decoding requires draft_params "
                             "(compress the same checkpoint with a draft "
                             "profile: core.model_compress.compress_draft)")
        self.cfg = cfg
        self.params = params
        self.draft_params = draft_params
        self.ecfg = engine_cfg
        self.sampling = sampling
        self.api = api
        self.spec = spec
        if self._spec_tree:
            from repro.engine.spec import TreeTemplate
            fan = tuple(int(f) for f in engine_cfg.spec_fanout)
            full = TreeTemplate(fan)
            # adaptive ladder: chain K=1 <- depth-equal chain <- full
            # tree. Rungs may coincide (e.g. a depth-1 fanout's mid rung
            # IS the low one) — kept positional, not deduped, so the
            # LOW/HIGH thresholds always map to the right rung; the
            # jitted step triple is lru-memoized per fanout, so
            # duplicate rungs never recompile.
            self._fanout_ladder = [(1,), (1,) * full.depth, fan] \
                if engine_cfg.spec_adaptive else [fan]
            lookahead = full.n_nodes       # verify writes all N tree slots
            self._spec_width = full.depth + 1
            self._tree_depth = full.depth
        else:
            lookahead = engine_cfg.spec_k
            self._spec_width = engine_cfg.spec_k + 1
        self._full_lookahead = lookahead
        self._accept_ewma = np.full((engine_cfg.num_slots,),
                                    self.SPEC_EWMA_INIT)
        # observability (DESIGN.md §10): one registry shared by the KV
        # cache, scheduler, spec ladder and metrics; tracing is off by
        # default and never changes the dispatch/sync structure
        self.tel = telemetry if telemetry is not None else Telemetry()
        reg = self.tel.registry
        self._c_retraces = reg.counter("jit.decode_retraces")
        self._c_ladder_flips = reg.counter("spec.ladder_transitions")
        self._g_ladder = reg.gauge("spec.ladder_rung")
        self._c_degraded = reg.counter("resil.degraded_segments")
        # chunked prefill (DESIGN.md §14): chunk dispatches + requests
        # preempted while still mid-prefill (their fold is empty — the
        # re-prefill restarts the chunk ladder from the fold point)
        self._c_chunks = reg.counter("engine.prefill_chunks")
        self._c_chunk_tokens = reg.counter("engine.prefill_chunk_tokens")
        self._c_midprefill_preempt = reg.counter(
            "resil.midprefill_preemptions")
        # work as dispatched: the rows every prefill dispatch computes
        # (padding included) against the real prompt tokens it feeds,
        # and the KV pages each plain decode step's attention grid spans
        # (slots x max_live) against those that hold keys
        self._c_prefill_rows = reg.counter("engine.prefill_rows")
        self._c_prefill_tokens = reg.counter("engine.prefill_tokens")
        self._c_pages_spanned = reg.counter("engine.decode_pages_spanned")
        self._c_pages_live = reg.counter("engine.decode_pages_live")
        # MoE dispatch (models/moe.py:moe_serve): routed entries (real
        # tokens x top_k x MoE layers) against the rows the expert
        # kernels compute, per prefill and per decode dispatch
        self._c_moe = {kind: (reg.counter(f"moe.routed_rows.{kind}"),
                              reg.counter(f"moe.expert_rows.{kind}"))
                       for kind in ("prefill", "decode")} \
            if cfg.moe is not None else None
        self._ladder_rung: Optional[int] = None
        self.rcfg = engine_cfg.resilience if engine_cfg.resilience \
            is not None else ResilienceConfig()
        self.chaos = make_injector(self.rcfg.chaos, reg)
        self.kv = PagedKVCache(cfg, api, engine_cfg.num_slots,
                               engine_cfg.max_seq, engine_cfg.page_size,
                               engine_cfg.num_pages,
                               lookahead=lookahead, registry=reg,
                               prefix_cache=engine_cfg.prefix_cache)
        self.kv.chaos = self.chaos
        self.scheduler = Scheduler(engine_cfg.num_slots, self.kv,
                                   engine_cfg.max_seq, registry=reg)
        self.metrics = EngineMetrics(registry=reg, tracer=self.tel.tracer)
        self._rng = jax.random.PRNGKey(engine_cfg.seed)
        b = engine_cfg.num_slots
        self._tokens = jnp.zeros((b,), jnp.int32)      # device-side feedback
        self._positions = jnp.zeros((b,), jnp.int32)
        self._active = jnp.zeros((b,), jnp.int32)
        self._remaining = jnp.zeros((b,), jnp.int32)   # per-slot budget left
        self._block_tables = self.kv.device_block_tables()
        self._max_live = self.kv.max_pages_per_slot    # static, pow2-bucketed
        self._source = None              # timed-admission stream, run() only
        # two-deep dispatch (DESIGN.md §14): token arrays of decode
        # segments dispatched but not yet synced, each with the rids of
        # the requests whose last token it holds (their finish is
        # stamped when it is retired). Each boundary retires the
        # PREVIOUS segment (trailing copy) and leaves the one just
        # dispatched in flight — at most one entry deep, so the host is
        # always scheduling segment N+1 while N executes.
        self._inflight: Deque[Tuple[jnp.ndarray, List[int]]] = deque()
        self._token_log: List[jnp.ndarray] = []        # [B] arrays, lazy
        # spec mode log: (tokens [B, W], counts [B]) per prefill/round
        self._spec_log: List = []
        self._use_pallas = kernels_on()
        self._prefill_fn, self._decode_fn, self._tail_fn = _step_fns(
            cfg, sampling, self._use_pallas)
        if self.spec and not self._spec_tree:
            from repro.engine.spec import spec_step_fns
            self._draft_fn, self._verify_fn = spec_step_fns(
                cfg, sampling, self._use_pallas, engine_cfg.spec_k,
                engine_cfg.spec_draft_layers)

    # -- API ----------------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               arrival_t: Optional[float] = None, priority: int = 0,
               deadline_t: Optional[float] = None) -> int:
        """Enqueue a request. ``arrival_t`` (a ``metrics.now()``-clock
        timestamp) backdates the enqueue to the request's TRUE arrival —
        the timed-admission loop polls its source at scheduling
        boundaries, so a request can arrive well before it is submitted,
        and queue wait / TTFT must be measured from arrival, not from
        the boundary that happened to notice it.

        ``priority``: admission band (higher served first; strictly
        higher may preempt, DESIGN.md §12.1). ``deadline_t``: absolute
        TTFT deadline on the metrics clock — a queued request past it is
        shed instead of served (defaults from the resilience config's
        ``deadline_ttft_ms``, measured from arrival). Malformed requests
        raise :class:`~repro.engine.resilience.RejectedRequest` and are
        never enqueued."""
        if deadline_t is None and self.rcfg.deadline_ttft_ms is not None:
            base = arrival_t if arrival_t is not None \
                else self.metrics.now()
            deadline_t = base + self.rcfg.deadline_ttft_ms / 1e3
        rid = self.scheduler.submit(prompt, max_new_tokens,
                                    arrival_t=arrival_t,
                                    priority=priority,
                                    deadline_t=deadline_t)
        self.metrics.record_enqueue(rid, t=arrival_t)
        return rid

    def run(self, source=None) -> Dict:
        """Serve until the queue and all slots drain. Returns
        {"results": [...], "metrics": {...}} (results in completion order).

        ``source`` (an :class:`~repro.engine.loadgen.ArrivalSource`)
        switches the loop to *timed admission* (open-loop serving,
        DESIGN.md §11): instead of draining a pre-submitted queue, the
        loop polls the source at every scheduling boundary, submits the
        requests whose arrival times have passed (backdated to their
        true arrivals), sleeps until the next arrival when idle, and
        feeds completions back (closed-loop sources schedule their next
        request off them). Requests therefore arrive MID-RUN, decode
        segments get interrupted by admissions, and queue wait measures
        real backpressure — the regime every SLO number must come from.
        """
        sch = self.scheduler
        tracer = self.tel.tracer
        self._source = source
        self.metrics.run_started()
        t0 = self.metrics.start_t
        interrupted = False
        try:
            while sch.has_work() or (source is not None
                                     and not source.exhausted):
                sch.tick_quarantine()
                if source is not None:
                    now = self.metrics.now()
                    for g in source.due(now - t0):
                        arr = t0 + g.arrival_s if g.arrival_s is not None \
                            else now
                        self.submit(g.prompt, g.max_new, arrival_t=arr,
                                    priority=getattr(g, "priority", 0))
                self._shed_pass(t0)
                if self.chaos is not None:
                    spike = self.chaos.latency_spike_s()
                    if spike > 0:
                        time.sleep(spike)
                la = self._admission_lookahead()
                with tracer.span("admit") as sp:
                    admitted = sch.admit(lookahead=la)
                    preempted = self._maybe_preempt(la)
                    if preempted:
                        admitted += sch.admit(lookahead=la)
                    sp.set(admitted=len(admitted), preempted=preempted,
                           queue_depth=len(sch.waiting))
                if admitted:
                    self._do_prefill(admitted)
                # chunked prefill (DESIGN.md §14): every PREFILLING slot
                # advances one prompt chunk per boundary; the final
                # chunk's sample is the first token and flips the slot
                # to DECODE in time for this boundary's segment
                self._feed_prefill_chunks()
                prefilling = any(r.state == PREFILLING
                                 for r in sch.active())
                actives = [r for r in sch.active() if r.state == DECODE]
                if not actives:
                    if sch.waiting and not sch.active():
                        head = sch.waiting[0]
                        need = self.kv.pages_needed(head.total_tokens,
                                                    lookahead=0)
                        if need > self.kv.num_pages:
                            # physically impossible, with the whole pool
                            # free — not backpressure, a config error
                            raise RuntimeError(
                                f"request {head.rid} needs {need} pages "
                                f"but the pool only has "
                                f"{self.kv.num_pages}")
                        # transient block (quarantined slots, injected
                        # alloc failure): retry at the next boundary
                        time.sleep(0.0005)
                        continue
                    if source is not None and not sch.has_work():
                        self._wait_for_arrival(source, t0)
                    continue
                if self.chaos is not None \
                        and self.chaos.cfg.nan_logits > 0:
                    pre_prod = {r.rid: r.produced for r in actives}
                else:
                    pre_prod = None
                # spec ladder interplay (DESIGN.md §14): no draft/verify
                # while any slot is mid-chunk — a plain one-step segment
                # keeps the chunk cadence token-granular, and plain
                # decode is the (lossless) floor of the degrade ladder
                if self.spec and not prefilling:
                    finished = self._spec_segment(actives)
                else:
                    finished = self._decode_segment(
                        actives, max_steps=1 if prefilling else None)
                if pre_prod is not None:
                    finished = self._inject_nan(actives, finished,
                                                pre_prod)
                t = self.metrics.now()
                with tracer.span("evict", evicted=len(finished)):
                    for r in finished:
                        self.metrics.count_finish(r.rid, r.produced)
                        sch.finish(r)
                        if source is not None:
                            source.on_finish(t - t0)
                        # an evicted slot's acceptance history dies with it
                        self._accept_ewma[r.slot] = self.SPEC_EWMA_INIT
                    self._finish_at_completion([r.rid for r in finished])
                    if finished:
                        self._sync_slot_state()
                self.tel.maybe_stats(self.metrics)
        except KeyboardInterrupt:
            # graceful shutdown (DESIGN.md §12): shed the queue, account
            # the in-flight requests with their tokens so far, free every
            # page — the caller still gets results/metrics/trace flushed
            interrupted = True
            self._drain_on_interrupt()
        self.metrics.run_finished()
        self._retire_inflight()
        out = {"results": self._materialize(), "metrics":
               self.metrics.summary()}
        if interrupted:
            out["interrupted"] = True
        return out

    def _shed_pass(self, t0: float) -> None:
        """Boundary shed: drop queued requests whose TTFT deadline has
        already passed (first-class verdicts, DESIGN.md §12)."""
        sch = self.scheduler
        if not self.rcfg.shed or not sch.waiting:
            return
        now = self.metrics.now()
        for r in sch.shed_expired(now):
            self.metrics.record_shed(r.rid, now, "deadline")
            if self._source is not None:   # keep closed loops flowing
                self._source.on_finish(now - t0)

    def _admission_lookahead(self) -> Optional[int]:
        """Pressure-degraded admission (DESIGN.md §12.2): under KV-pool
        pressure, new reservations shrink their speculative lookahead
        (full -> chain K=1 -> none) so the pool serves more concurrent
        requests before any preemption fires. None = the full default."""
        if not self.spec or not self.rcfg.pressure_degrade:
            return None
        sch = self.scheduler
        head_blocked = bool(sch.waiting) and not self.kv.can_admit(
            sch.waiting[0].total_tokens, prompt=sch.waiting[0].prompt)
        lvl = pressure_level(self.kv, head_blocked,
                             self.rcfg.pressure_occupancy)
        if lvl == PRESSURE_CRITICAL:
            return 0
        if lvl == PRESSURE_ELEVATED:
            return 1
        return None

    def _maybe_preempt(self, la: Optional[int]) -> int:
        """KV-pressure preemption (DESIGN.md §12.1): the queue head has a
        free slot but cannot reserve pages — release strictly-lower-
        priority victims (their tokens fold into their prompts for
        lossless recompute) until it can. Returns the victim count."""
        sch = self.scheduler
        if not self.rcfg.preempt or not sch.waiting:
            return 0
        slot_free = any(s.free and i not in sch._quarantine
                        for i, s in enumerate(sch.slots))
        la_eff = self.kv.lookahead if la is None else la
        head = sch.waiting[0]
        if not slot_free or self.kv.can_admit(head.total_tokens, la_eff,
                                              prompt=head.prompt):
            return 0
        # mid-prefill slots are preemptible too (DESIGN.md §14): a
        # PREFILLING victim has the least sunk work per freed page (its
        # fold is empty — produced == folded — so recompute restarts
        # the chunk ladder from the fold point, losslessly)
        running = [r for r in sch.active()
                   if r.state in (DECODE, PREFILLING)]
        victims = choose_victims(head, running, self.kv, la_eff,
                                 self.rcfg.max_preemptions)
        for v in victims:
            self._preempt_request(v, "kv_pressure")
        return len(victims)

    def _drain_on_interrupt(self) -> None:
        """SIGINT landed mid-run: drop the queue (shed verdicts), account
        every in-flight request's tokens so far, release all pages."""
        sch = self.scheduler
        t = self.metrics.now()
        t0 = self.metrics.start_t or t
        for r in sch.shed_all():
            self.metrics.record_shed(r.rid, t, "shutdown")
            if self._source is not None:
                self._source.on_finish(t - t0)
        done = []
        for r in list(sch.active()):
            if r.state == DECODE and r.produced > 0:
                self.metrics.count_finish(r.rid, r.produced)
                done.append(r.rid)
            sch.finish(r)
        self._finish_at_completion(done)

    def _request_tokens(self, r: Request) -> np.ndarray:
        """Materialize the tokens ``r`` generated since its last fold
        (host sync — preemption is a slow path, not the decode loop)."""
        if not r.log_entries:
            return np.zeros((0,), np.int32)
        if self.spec:
            parts = []
            for i in r.log_entries:
                toks, cnt = self._spec_log[i]
                c = int(np.asarray(cnt)[r.slot])
                if c > 0:
                    parts.append(np.asarray(toks)[r.slot, :c])
            out = np.concatenate(parts) if parts \
                else np.zeros((0,), np.int32)
        else:
            mat = np.asarray(jnp.stack([self._token_log[i]
                                        for i in r.log_entries]))
            out = mat[:, r.slot]
        return out[:r.produced - r.folded].astype(np.int32)

    def _preempt_request(self, r: Request, reason: str) -> None:
        """Preempt-and-recompute (DESIGN.md §12.1): fold the tokens
        generated so far into the prompt and re-enqueue. Greedy prefill
        over (prompt + generated) writes the exact K/V a continued
        decode would have (the engine-vs-naive-forward parity test pins
        this), so the re-prefill resumes the request losslessly —
        bit-identical greedy outputs, pinned by test."""
        if r.state == PREFILLING:
            self._c_midprefill_preempt.inc()
        r.prompt = np.concatenate([r.prompt, self._request_tokens(r)]) \
            .astype(np.int32)
        r.folded = r.produced
        self.metrics.record_preempt(r.rid)
        self.tel.tracer.instant("preempt", rid=r.rid, reason=reason)
        self.scheduler.preempt(r)
        self._sync_slot_state()

    def _inject_nan(self, actives: List[Request], finished: List[Request],
                    pre_prod: Dict[int, int]) -> List[Request]:
        """Chaos ``nan_logits`` (DESIGN.md §12.3): a poisoned sampler for
        one slot's segment. Recovery = drop the segment's tokens for
        that slot (rewind to the pre-segment count; materialization
        trims to ``produced``), quarantine the slot for a few
        boundaries, and re-enqueue the request for lossless recompute —
        greedy outputs stay bit-identical to a fault-free run."""
        sch = self.scheduler
        for r in actives:
            if not self.chaos.fires("nan_logits"):
                continue
            r.produced = pre_prod[r.rid]
            if r in finished:
                finished.remove(r)
            slot = r.slot
            self._preempt_request(r, "nan_quarantine")
            sch.quarantine_slot(slot,
                                self.chaos.cfg.quarantine_boundaries)
        return finished

    def _wait_for_arrival(self, source, t0: float) -> None:
        """Engine idle, stream not exhausted: sleep until the next
        arrival is due (capped so a closed-loop source whose next due
        time depends on a completion re-polls promptly)."""
        nxt = source.next_at()
        if nxt is None:
            return
        dt = (t0 + nxt) - self.metrics.now()
        if dt > 0:
            time.sleep(min(dt, 0.05))

    def _dispatch(self, fn, *args):
        """Dispatch one jitted step, with chaos device-error injection +
        bounded exponential-backoff retry (the ``dist.fault.retrying``
        discipline). Safe to retry unconditionally: every step is
        functional — engine state is assigned only from its returns, so
        a failed dispatch leaves nothing half-written."""
        chaos = self.chaos
        if chaos is None or chaos.cfg.device_err <= 0:
            return fn(*args)
        attempt = 0
        while True:
            try:
                if chaos.fires("device_err"):
                    raise ChaosDeviceError("chaos: injected device error")
                return fn(*args)
            except ChaosDeviceError:
                attempt += 1
                if attempt >= chaos.cfg.device_max_retries:
                    raise
                chaos.count_retry()
                if chaos.cfg.device_backoff_s > 0:
                    time.sleep(chaos.cfg.device_backoff_s
                               * (2 ** (attempt - 1)))

    def _count_decode_pages(self, actives: List[Request], seg: int) -> None:
        """Count the KV pages ``seg`` plain decode steps span (every
        slot's row of the block tables, clamped to ``max_live``) and
        those that hold keys once each step has written: a slot writing
        at position p holds ceil((p + 1) / page_size) pages. In closed
        form per slot, since every active slot runs all ``seg`` steps."""
        ps = self.ecfg.page_size

        def pages_upto(n: int) -> int:     # sum of ceil(m / ps), m = 1..n
            q, rem = divmod(n, ps)
            return ps * q * (q + 1) // 2 + rem * (q + 1)
        live = 0
        for r in actives:
            p0 = self.scheduler.slots[r.slot].position
            live += pages_upto(p0 + seg) - pages_upto(p0)
        self._c_pages_live.inc(live)
        self._c_pages_spanned.inc(seg * self.ecfg.num_slots * self._max_live)

    def _count_moe(self, kind: str, tokens: int, rows: int,
                   dispatches: int = 1) -> None:
        """Count an MoE model's ``dispatches`` of ``rows`` token rows that
        carry ``tokens`` real tokens in all: their routed entries, and
        the rows the expert kernels compute for them (static shapes)."""
        if self._c_moe is None:
            return
        layers = self.cfg.n_layers - self.cfg.n_dense_layers
        routed, computed = self._c_moe[kind]
        routed.inc(tokens * self.cfg.moe.top_k * layers)
        computed.inc(dispatches * expert_rows(self.cfg, rows) * layers)

    def _stamp_finishes(self, rids: List[int]) -> None:
        """Stamp the finish of requests whose last token the host has
        just seen ready."""
        if rids:
            t = self.metrics.now()
            for rid in rids:
                self.metrics.stamp_finish(rid, t)

    def _finish_at_completion(self, rids: List[int]) -> None:
        """Requests evicted at this boundary finish when their last
        token exists: with the last decode segment's tokens in flight,
        their stamp waits for the sync that retires it; with nothing in
        flight (a spec segment's own sync, a prefill's) it is now."""
        if self._inflight:
            self._inflight[-1][1].extend(rids)
        else:
            self._stamp_finishes(rids)

    def _retire_inflight(self) -> None:
        """The drain when ``run`` ends: read the token arrays still in
        flight to the host (materialization reads them anyway) and stamp
        the finishes waiting on them."""
        if not self._inflight:
            return
        jax.device_get([toks for toks, _ in self._inflight])
        self._stamp_finishes([rid for _, rids in self._inflight
                              for rid in rids])
        self._inflight.clear()

    def _decode_segment(self, actives: List[Request],
                        max_steps: Optional[int] = None) -> List[Request]:
        """Plain decode segment: no slot can exceed its budget before the
        earliest one finishes, so no host sync inside the segment. Also
        the floor of the spec degrade ladder — when a spec engine runs it
        (some slot's reservation has no lookahead), tokens log into the
        spec log (width 1) so materialization stays uniform.

        ``max_steps`` clamps the segment (chunked prefill runs one-step
        segments so prompt chunks interleave at token granularity).

        Two-deep dispatch (DESIGN.md §14): the boundary does NOT wait
        for this segment's tokens — it retires the *previous* segment's
        final array (a trailing copy, typically already complete since
        this segment's dispatches queued behind it) and leaves this one
        in flight. Host accounting needs no token values (budgets are
        host-side counters; values are only read at materialization or
        a preemption fold, both of which sync implicitly), so the host
        is always one segment ahead of the device — and issues strictly
        fewer ``block_until_ready`` calls than segments dispatched,
        pinned by the telemetry sync-count test."""
        sch = self.scheduler
        tracer = self.tel.tracer
        t0 = self.metrics.now()
        seg = max(1, min(r.remaining for r in actives))
        if max_steps is not None:
            seg = min(seg, max_steps)
        self._count_decode_pages(actives, seg)
        self._count_moe("decode", seg * len(actives), self.ecfg.num_slots,
                        dispatches=seg)
        finished: List[Request] = []
        with tracer.span("decode_segment", steps=seg, slots=len(actives),
                         tokens=seg * len(actives)) as seg_sp:
            for _ in range(seg):
                self._tokens, self._positions, self.kv.data, \
                    self._rng = self._dispatch(
                        self._decode_fn,
                        self.params, self.kv.data, self._tokens,
                        self._positions, self._block_tables,
                        self._active, self._rng, self._max_live)
                if self.spec:
                    idx = self._log_spec(self._tokens[:, None],
                                         self._active)
                else:
                    idx = len(self._token_log)
                    self._token_log.append(self._tokens)
                for r in sch.active():
                    if r.state == DECODE:
                        r.log_entries.append(idx)
                finished.extend(sch.step_decoded())
        self._inflight.append((self._tokens, []))
        if len(self._inflight) > 1:
            with tracer.span("sync", cat="sync"):
                while len(self._inflight) > 1:
                    toks, rids = self._inflight.popleft()
                    jax.block_until_ready(toks)
                    self._stamp_finishes(rids)
        if tracer.enabled:
            for r in actives:
                tracer.flow_point(r.rid, "decode_segment", t=seg_sp.t0)
        self.metrics.decode_steps += seg
        self.metrics.record_decode_segment(self.metrics.now() - t0,
                                           seg * len(actives))
        return finished

    def _spec_segment(self, actives: List[Request]) -> List[Request]:
        """Speculative segment: interleave fused draft calls with one
        multi-token verify call per round. Every round emits 1..K+1
        tokens per active slot (K = chain length or tree depth,
        device-clamped to the slot's budget), so
        ceil(min_remaining / (K+1)) rounds can never overshoot the
        earliest budget — the host syncs once at the boundary, exactly
        like the plain segment loop. Tree mode additionally picks the
        segment's fanout profile from the adaptive ladder (the jitted
        step pairs are memoized per fanout, so profile flips never
        recompile)."""
        sch = self.scheduler
        tracer = self.tel.tracer
        t0 = self.metrics.now()
        # pressure degrade (DESIGN.md §12.2): the segment's speculative
        # shape may not write past the SMALLEST lookahead reservation
        # among its active slots — degraded admissions clamp the whole
        # segment (to chain K=1, or to plain decode at lookahead 0)
        seg_la = min(self.kv.slot_lookahead(r.slot) for r in actives)
        if seg_la < self._full_lookahead:
            self._c_degraded.inc()
            if seg_la <= 0:
                return self._decode_segment(actives)
        if self._spec_tree:
            from repro.engine.spec import tree_step_fns
            if seg_la >= self._full_lookahead:
                fanout = self._segment_fanout()
            else:
                # deepest chain whose tentative verify writes fit the
                # smallest reservation
                fanout = (1,) * min(seg_la, self._tree_depth)
            draft_fn, verify_fn, tpl = tree_step_fns(
                self.cfg, self.sampling, self._use_pallas, fanout,
                self.ecfg.spec_draft_layers)
            k, width = tpl.depth, tpl.n_nodes + 1
            draft_dispatches = tpl.depth          # root + frontier calls
        else:
            k = min(self.ecfg.spec_k, seg_la)
            if k == self.ecfg.spec_k:
                draft_fn, verify_fn = self._draft_fn, self._verify_fn
            else:
                from repro.engine.spec import spec_step_fns
                draft_fn, verify_fn = spec_step_fns(
                    self.cfg, self.sampling, self._use_pallas, k,
                    self.ecfg.spec_draft_layers)
            width = k + 1
            draft_dispatches = 1                  # one fused K-step call
        rounds = max(1, -(-min(r.remaining for r in actives) // (k + 1)))
        round_idxs: List[int] = []
        with tracer.span("spec_segment") as seg_sp:
            for _ in range(rounds):
                # per-round spans are dispatch-only (cat "dispatch"): the
                # segment stays sync-free, so they time async enqueue,
                # not device work — the device side comes from the
                # profiler annotations / named scopes
                with tracer.span("draft", cat="dispatch"):
                    draft = self._dispatch(
                        draft_fn,
                        self.draft_params, self.kv.data, self._tokens,
                        self._positions, self._block_tables,
                        self._max_live)
                with tracer.span("verify", cat="dispatch"):
                    (out, n_new, self._tokens, self._positions,
                     self._remaining, self.kv.data, self._rng) = \
                        self._dispatch(
                        verify_fn,
                        self.params, self.kv.data, self._tokens, draft,
                        self._positions, self._block_tables, self._active,
                        self._remaining, self._rng, self._max_live)
                idx = self._log_spec(out, n_new)
                round_idxs.append(idx)
                for r in sch.active():
                    if r.state == DECODE:
                        r.log_entries.append(idx)
            with tracer.span("sync", cat="sync"):
                jax.block_until_ready(self._tokens)    # segment boundary
            # the round replay below reads n_new on the host, so spec
            # segments sync at their own boundary — anything a plain
            # segment left in flight is older than this sync (one
            # device stream) and retires with it
            self._stamp_finishes([rid for _, rids in self._inflight
                                  for rid in rids])
            self._inflight.clear()
            seg_tokens = 0
            for idx in round_idxs:                     # replay the rounds
                n_new_h = np.asarray(self._spec_log[idx][1])
                proposed, accepted = sch.step_spec_round(n_new_h, k)
                slot_rounds = int((n_new_h > 0).sum())
                self.metrics.record_spec_round(
                    proposed, accepted, slot_rounds=slot_rounds,
                    verify_tokens=width * slot_rounds)
                if self.ecfg.spec_adaptive:
                    self._update_accept_ewma(n_new_h, k)
                seg_tokens += int(n_new_h.sum())
            seg_sp.set(rounds=rounds, k=k, slots=len(actives),
                       tokens=seg_tokens)
            if tracer.enabled:
                for r in actives:
                    tracer.flow_point(r.rid, "spec_segment", t=seg_sp.t0)
        # draft dispatches + verify dispatches (for dispatch accounting;
        # spec_rounds tracks rounds)
        self.metrics.decode_steps += (draft_dispatches + 1) * rounds
        self.metrics.record_decode_segment(self.metrics.now() - t0,
                                           seg_tokens)
        return sch.collect_finished()

    def _segment_fanout(self) -> Tuple[int, ...]:
        """Adaptive tree budget: the MIN of the active slots' acceptance
        EWMAs picks the ladder rung (conservative — thrash anywhere
        shrinks the whole batch's tree; the tree shape is one static
        jitted program per segment, so per-slot budgets resolve at
        segment granularity)."""
        if len(self._fanout_ladder) == 1:
            return self._pick_rung(0)
        act = [i for i, s in enumerate(self.scheduler.slots)
               if s.request is not None and s.request.state == DECODE]
        a = min(self._accept_ewma[i] for i in act) if act else 1.0
        if a < self.SPEC_EWMA_LOW:
            return self._pick_rung(0)
        if a >= self.SPEC_EWMA_HIGH:
            return self._pick_rung(2)
        return self._pick_rung(1)

    def _pick_rung(self, idx: int) -> Tuple[int, ...]:
        """Publish the chosen ladder rung: transition counter + gauge +
        a trace instant marking the segment where the tree reshaped."""
        if idx != self._ladder_rung:
            if self._ladder_rung is not None:
                self._c_ladder_flips.inc()
            self._ladder_rung = idx
            self.tel.tracer.instant(
                "spec_ladder", rung=idx,
                fanout=str(self._fanout_ladder[idx]))
        self._g_ladder.set(idx)
        return self._fanout_ladder[idx]

    def _update_accept_ewma(self, n_new: np.ndarray, k: int) -> None:
        """Fold one round's per-slot acceptance fraction ((n_new - 1)/K,
        the budget-clamp tail reads as rejection — acceptable noise for a
        control signal) into the per-slot EWMAs."""
        reg = self.tel.registry
        for i in range(self.ecfg.num_slots):
            if n_new[i] > 0:
                rate = min(max((float(n_new[i]) - 1.0) / max(k, 1), 0.0),
                           1.0)
                self._accept_ewma[i] = (self.SPEC_EWMA_BETA
                                        * self._accept_ewma[i]
                                        + (1 - self.SPEC_EWMA_BETA) * rate)
                reg.gauge(f"spec.accept_ewma.slot{i}").set(
                    float(self._accept_ewma[i]))

    # -- internals ----------------------------------------------------------

    def _do_prefill(self, admitted: List[Request]) -> None:
        b = self.ecfg.num_slots
        tracer = self.tel.tracer
        # chunked prefill (DESIGN.md §14): an admitted prompt whose
        # unshared tail exceeds the chunk budget does NOT prefill here —
        # it enters PREFILLING and feeds one chunk per scheduling
        # boundary (_feed_prefill_chunks), interleaved with the other
        # slots' decode steps. Tails that fit one chunk keep the
        # monolithic paths below (their cost is bounded by the budget,
        # and the batched flash prefill keeps its MFU).
        budget = self.ecfg.prefill_chunk_tokens
        if budget > 0:
            rest = []
            for r in admitted:
                sh = self.kv.slot_shared_tokens(r.slot)
                if len(plan_chunks(sh, r.prompt_len, budget)) > 1:
                    r.state = PREFILLING
                    r.prefill_pos = sh
                    self.metrics.record_admit(r.rid)
                else:
                    rest.append(r)
            admitted = rest
            if not admitted:
                # PREFILLING slots changed the admission picture (their
                # device rows must mask out of decode dispatches)
                self._sync_slot_state()
                return
        # prefix-cache split (DESIGN.md §13): slots whose prompt prefix
        # was mapped to cached pages at admission prefill only the
        # unshared tail (a ragged multi-token decode block against the
        # already-populated paged prefix); the rest take the batched
        # flash prefill as before. Two dispatch groups, one boundary.
        shared = [r for r in admitted
                  if self.kv.slot_shared_tokens(r.slot) > 0]
        full = [r for r in admitted
                if self.kv.slot_shared_tokens(r.slot) == 0]
        merged = self._tokens
        lengths_all = np.zeros((b,), np.int32)
        mask_all = np.zeros((b,), bool)
        idx_of: Dict[int, int] = {}       # rid -> token-log index
        for r in admitted:
            self.metrics.record_admit(r.rid)
            lengths_all[r.slot] = r.prompt_len
            mask_all[r.slot] = True
        if full:
            # cap the pow2 bucket at max_seq: prompt_len <= max_seq is
            # enforced at submit, wider buckets are pure waste
            s = min(_bucket(max(r.prompt_len for r in full),
                            self.ecfg.prompt_bucket_min), self.ecfg.max_seq)
            tokens = np.zeros((b, s), np.int32)
            lengths = np.zeros((b,), np.int32)
            # non-group slots must be invisible to the prefill scatter:
            # their rows get length 0 + all-sentinel block tables
            bt = np.full_like(self.kv.block_tables, self.kv.sentinel)
            mask = np.zeros((b,), bool)
            for r in full:
                tokens[r.slot, :r.prompt_len] = r.prompt
                lengths[r.slot] = r.prompt_len
                bt[r.slot] = self.kv.block_tables[r.slot]
                mask[r.slot] = True
            with tracer.span("prefill", admitted=len(full), bucket=s,
                             tokens=len(full),
                             prompt_tokens=int(lengths.sum())) as sp:
                first, self.kv.data, self._rng = self._dispatch(
                    self._prefill_fn,
                    self.params, self.kv.data, jnp.asarray(tokens),
                    jnp.asarray(lengths), jnp.asarray(bt), self._rng)
                self._c_prefill_rows.inc(b * s)
                self._c_prefill_tokens.inc(int(lengths.sum()))
                self._count_moe("prefill", int(lengths.sum()), b * s)
                with tracer.span("sync", cat="sync"):
                    jax.block_until_ready(first)
                if tracer.enabled:
                    for r in full:
                        tracer.flow_point(r.rid, "prefill", t=sp.t0)
            if self.spec:
                idx = self._log_spec(first[:, None],
                                     jnp.asarray(mask.astype(np.int32)))
            else:
                idx = len(self._token_log)
                self._token_log.append(first)
            for r in full:
                idx_of[r.rid] = idx
            merged = jnp.where(jnp.asarray(mask), first, merged)
        if shared:
            # unshared tails, padded to one pow2 T; feed_len masks the
            # padding's K/V writes (sentinel convention), so rows of
            # different tail lengths ride one dispatch safely
            t_pad = min(_bucket(max(r.prompt_len
                                    - self.kv.slot_shared_tokens(r.slot)
                                    for r in shared),
                                self.ecfg.prompt_bucket_min),
                        self.ecfg.max_seq)
            toks = np.zeros((b, t_pad), np.int32)
            starts = np.zeros((b,), np.int32)
            feed = np.zeros((b,), np.int32)
            bt = np.full_like(self.kv.block_tables, self.kv.sentinel)
            mask = np.zeros((b,), bool)
            hit_tokens = 0
            for r in shared:
                sh = self.kv.slot_shared_tokens(r.slot)
                n = r.prompt_len - sh
                toks[r.slot, :n] = r.prompt[sh:]
                starts[r.slot] = sh
                feed[r.slot] = n
                bt[r.slot] = self.kv.block_tables[r.slot]
                mask[r.slot] = True
                hit_tokens += sh
            occ = int((bt != self.kv.sentinel).sum(1).max())
            max_live = min(_bucket(max(occ, 1), 1),
                           self.kv.max_pages_per_slot)
            with tracer.span("prefill_tail", admitted=len(shared),
                             bucket=t_pad, tail_tokens=int(feed.sum()),
                             shared_tokens=hit_tokens) as sp:
                first_t, self.kv.data, self._rng = self._dispatch(
                    self._tail_fn,
                    self.params, self.kv.data, jnp.asarray(toks),
                    jnp.asarray(starts), jnp.asarray(feed),
                    jnp.asarray(bt), self._rng, max_live)
                self._c_prefill_rows.inc(b * t_pad)
                self._c_prefill_tokens.inc(int(feed.sum()))
                self._count_moe("prefill", int(feed.sum()), b * t_pad)
                with tracer.span("sync", cat="sync"):
                    jax.block_until_ready(first_t)
                if tracer.enabled:
                    for r in shared:
                        tracer.flow_point(r.rid, "prefill_tail", t=sp.t0)
            if self.spec:
                idx = self._log_spec(first_t[:, None],
                                     jnp.asarray(mask.astype(np.int32)))
            else:
                idx = len(self._token_log)
                self._token_log.append(first_t)
            for r in shared:
                idx_of[r.rid] = idx
            merged = jnp.where(jnp.asarray(mask), first_t, merged)
        # the prompts' full-page K/V blocks are now all written (cached
        # prefix + freshly prefilled remainder): cache them BEFORE any
        # budget-exhausted request below releases its pages
        if self.kv.prefix is not None:
            for r in admitted:
                self.kv.prefix_insert(r.slot, r.prompt)
        t = self.metrics.now()
        done_now = []
        for r in admitted:
            r.state = DECODE
            # prefill produced the NEXT token: #1 for a fresh request,
            # #folded+1 for a preempted one resuming from its folded
            # prompt (produced == folded at re-admission)
            r.produced += 1
            r.log_entries = [idx_of[r.rid]]
            self.metrics.record_first_token(r.rid, t)
            if r.produced >= r.max_new_tokens:   # budget exhausted already
                self.metrics.record_finish(r.rid, t, r.produced)
                done_now.append(r)
        for r in done_now:
            self.scheduler.finish(r)
            if self._source is not None:   # closed-loop completion feedback
                self._source.on_finish(t - self.metrics.start_t)
        # merge the admitted slots into the device-side decode state
        self._tokens = merged
        self._positions = jnp.where(jnp.asarray(mask_all),
                                    jnp.asarray(lengths_all),
                                    self._positions)
        self._sync_slot_state()

    def _feed_prefill_chunks(self) -> None:
        """Advance every PREFILLING slot by one prompt chunk (DESIGN.md
        §14). A chunk is a ragged ``tail_fn`` feed whose start is the
        previous chunk's end — exactly the prefix-cache tail-prefill
        dispatch, so the kernels need no new mode and a prefix-shared
        prompt's first chunk simply starts at its shared boundary. All
        mid-chunk slots ride ONE dispatch; intermediate chunks discard
        the sampled token (it is not the first token — only the final
        chunk, which ends exactly at the prompt length, samples from
        the last real position and flips the request to DECODE via the
        same completion protocol as monolithic prefill). Intermediate
        chunks add no host sync: the dispatch queues behind the decode
        pipeline and the boundary's trailing sync covers it."""
        sch = self.scheduler
        chunking = [r for r in sch.active() if r.state == PREFILLING]
        if not chunking:
            return
        budget = self.ecfg.prefill_chunk_tokens
        b = self.ecfg.num_slots
        tracer = self.tel.tracer
        lens = {r.rid: plan_chunks(r.prefill_pos, r.prompt_len,
                                   budget)[0][1] for r in chunking}
        t_pad = min(_bucket(max(lens.values()),
                            self.ecfg.prompt_bucket_min),
                    self.ecfg.max_seq)
        toks = np.zeros((b, t_pad), np.int32)
        starts = np.zeros((b,), np.int32)
        feed = np.zeros((b,), np.int32)
        bt = np.full_like(self.kv.block_tables, self.kv.sentinel)
        finals: List[Request] = []
        for r in chunking:
            n = lens[r.rid]
            toks[r.slot, :n] = r.prompt[r.prefill_pos:r.prefill_pos + n]
            starts[r.slot] = r.prefill_pos
            feed[r.slot] = n
            # chunk-granular page exposure: only pages covering tokens
            # this chunk can touch (prefix + fed-so-far + the chunk)
            bt[r.slot] = self.kv.slot_block_table(r.slot,
                                                  r.prefill_pos + n)
            r.prefill_pos += n
            if r.prefill_pos >= r.prompt_len:
                finals.append(r)
        occ = int((bt != self.kv.sentinel).sum(1).max())
        max_live = min(_bucket(max(occ, 1), 1),
                       self.kv.max_pages_per_slot)
        with tracer.span("prefill_chunk", slots=len(chunking), bucket=t_pad,
                         chunk_tokens=int(feed.sum()),
                         completed=len(finals)) as sp:
            first_t, self.kv.data, self._rng = self._dispatch(
                self._tail_fn,
                self.params, self.kv.data, jnp.asarray(toks),
                jnp.asarray(starts), jnp.asarray(feed),
                jnp.asarray(bt), self._rng, max_live)
            self._c_prefill_rows.inc(b * t_pad)
            self._c_prefill_tokens.inc(int(feed.sum()))
            self._count_moe("prefill", int(feed.sum()), b * t_pad)
            if finals:
                # completed prefills take their TTFT timestamp here, so
                # the first token must actually exist (same convention
                # as the monolithic prefill block)
                with tracer.span("sync", cat="sync"):
                    jax.block_until_ready(first_t)
            if tracer.enabled:
                for r in chunking:
                    tracer.flow_point(r.rid, "prefill_chunk", t=sp.t0)
        self._c_chunks.inc(len(chunking))
        self._c_chunk_tokens.inc(int(feed.sum()))
        if not finals:
            return
        fmask = np.zeros((b,), bool)
        lengths = np.zeros((b,), np.int32)
        for r in finals:
            fmask[r.slot] = True
            lengths[r.slot] = r.prompt_len
        if self.spec:
            idx = self._log_spec(first_t[:, None],
                                 jnp.asarray(fmask.astype(np.int32)))
        else:
            idx = len(self._token_log)
            self._token_log.append(first_t)
        # prefix-insert timing audit (DESIGN.md §14): under chunking a
        # prompt's full-page blocks are only all written at its LAST
        # chunk — inserting earlier would cache pages whose K/V another
        # request could map before this slot writes them
        if self.kv.prefix is not None:
            for r in finals:
                self.kv.prefix_insert(r.slot, r.prompt)
        t = self.metrics.now()
        done_now = []
        for r in finals:
            r.state = DECODE
            r.produced += 1
            r.log_entries = [idx]
            self.metrics.record_first_token(r.rid, t)
            if r.produced >= r.max_new_tokens:   # budget exhausted already
                self.metrics.record_finish(r.rid, t, r.produced)
                done_now.append(r)
        for r in done_now:
            self.scheduler.finish(r)
            if self._source is not None:
                self._source.on_finish(t - self.metrics.start_t)
        self._tokens = jnp.where(jnp.asarray(fmask), first_t,
                                 self._tokens)
        self._positions = jnp.where(jnp.asarray(fmask),
                                    jnp.asarray(lengths),
                                    self._positions)
        self._sync_slot_state()

    def _log_spec(self, toks: jnp.ndarray, counts: jnp.ndarray) -> int:
        """Append a (tokens [B, W], counts [B]) pair to the spec log,
        width-padded to the max round width (chain K+1 / tree depth+1)
        so materialization is one stack per array."""
        w = self._spec_width
        if toks.shape[1] < w:
            toks = jnp.pad(toks, ((0, 0), (0, w - toks.shape[1])))
        self._spec_log.append((toks, counts))
        return len(self._spec_log) - 1

    def _sync_slot_state(self) -> None:
        """Refresh device copies of the block tables + active mask +
        per-slot budgets after a scheduling event (admission/eviction).

        PREFILLING slots (mid-chunk, DESIGN.md §14) get all-sentinel
        rows in the DECODE-side block tables: a decode/draft/verify
        dispatch samples every row, and without the mask its K/V
        scatter at the slot's stale position would corrupt the pages
        the chunk feeds are writing. Chunk dispatches build their own
        tables from the real ``kv.block_tables``."""
        # the copy is load-bearing under two-deep dispatch: jnp.asarray
        # of a host numpy array may be ZERO-COPY on CPU, and
        # kv.block_tables is mutated in place by assign/release — an
        # aliased device view would change under still-in-flight steps
        # (the old loop's per-boundary block_until_ready hid this)
        with self.tel.tracer.span("slot_sync"):
            bts = self.kv.block_tables.copy()
            mid = [i for i, s in enumerate(self.scheduler.slots)
                   if s.request is not None
                   and s.request.state == PREFILLING]
            if mid:
                bts[mid, :] = self.kv.sentinel
            self._block_tables = jnp.asarray(bts)
            # static clamp for the decode-side page gather / kernel grid:
            # the batch's max occupied page count, pow2-bucketed so the
            # jitted steps retrace at most log2(max_pages_per_slot) times
            occ = int((self.kv.block_tables
                       != self.kv.sentinel).sum(1).max())
            new_max_live = min(_bucket(max(occ, 1), 1),
                               self.kv.max_pages_per_slot)
            if new_max_live != self._max_live:
                # max_live is a static jit arg: every change retraces the
                # decode/draft/verify steps (pow2-bucketed, so bounded by
                # log2(max_pages_per_slot) over an engine lifetime)
                self._c_retraces.inc()
                self.tel.tracer.instant("jit_retrace",
                                        max_live=new_max_live)
            self._max_live = new_max_live
            act = np.zeros((self.ecfg.num_slots,), np.int32)
            rem = np.zeros((self.ecfg.num_slots,), np.int32)
            for i, slot in enumerate(self.scheduler.slots):
                if slot.request is not None \
                        and slot.request.state == DECODE:
                    act[i] = 1
                    rem[i] = slot.request.remaining
            self._active = jnp.asarray(act)
            self._remaining = jnp.asarray(rem)

    def _materialize(self) -> List[Dict]:
        """One host sync: stack the token log and slice every request's
        generated tokens out of it (completion order)."""
        if self.spec:
            return self._materialize_spec()
        if self._token_log:
            mat = np.asarray(jnp.stack(self._token_log))
        else:
            mat = np.zeros((0, self.ecfg.num_slots), np.int32)
        out = []
        for r in self.scheduler.finished:
            toks = mat[np.asarray(r.log_entries, np.int64), r.slot] \
                if r.log_entries else np.zeros((0,), np.int32)
            toks = toks[:r.produced - r.folded]
            if r.folded:
                # tokens generated before a preemption live in the folded
                # prompt — the output is their concatenation with the
                # post-resume log (DESIGN.md §12.1)
                toks = np.concatenate([r.prompt[r.orig_prompt_len:], toks])
            r.output = toks.astype(np.int32)
            out.append({"rid": r.rid, "prompt_len": r.orig_prompt_len,
                        "tokens": r.output, "n_generated": r.produced})
        return out

    def _materialize_spec(self) -> List[Dict]:
        """Spec-mode materialization: entries are (tokens [B, K+1],
        counts [B]) — a request's generation is the concatenation of its
        rounds' accepted slices (two host transfers total)."""
        if self._spec_log:
            mat = np.asarray(jnp.stack([a for a, _ in self._spec_log]))
            cnt = np.asarray(jnp.stack([c for _, c in self._spec_log]))
        else:
            mat = np.zeros((0, self.ecfg.num_slots, 1), np.int32)
            cnt = np.zeros((0, self.ecfg.num_slots), np.int32)
        out = []
        for r in self.scheduler.finished:
            if r.log_entries:
                toks = np.concatenate(
                    [mat[i, r.slot, :cnt[i, r.slot]] for i in r.log_entries])
            else:
                toks = np.zeros((0,), np.int32)
            toks = toks[:r.produced - r.folded]
            if r.folded:
                toks = np.concatenate([r.prompt[r.orig_prompt_len:], toks])
            r.output = toks.astype(np.int32)
            out.append({"rid": r.rid, "prompt_len": r.orig_prompt_len,
                        "tokens": r.output, "n_generated": r.produced})
        return out
