"""One cell of the benchmark: find its files by name, set up the served
model, run the measured window, reduce what it measured, and decide
``correct`` against the plain reference.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives
it: ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``layer_metrics/<metric>.py`` under the benchmark's directory. A
configuration names its model module, ``models/<name>.py`` (key
``"model"``), and its plain reference, ``references/<name>.py`` (key
``"reference"``).

A model module provides ``Model.from_conf(conf)``, an object with at
least ``vocab`` (readers get it as ``ctx.model``); ``program_config(conf)``,
the program's ``ModelConfig``; ``build_params(conf, cache_root, device,
workers) -> (params, parts)``, the served tree on the device and the
seconds of each part of making it; and optionally ``KERNELS``, kernel
names the breakdown keeps apart besides the harness's own.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import logging
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import traffic as traffic_mod
from bench.trace_reduce import Trace
from bench.weights import Compression

BENCH = os.path.dirname(os.path.abspath(__file__))
WINDOW = "bench_window"
KERNELS = ("gqsa_gemv", "paged_attention", "w4_matmul")
# host annotations of the engine that label the device's idle gaps
HOST_LABELS = ("prefill", "prefill_tail", "prefill_chunk",
               "decode_segment", "draft", "verify")
CHECK_REQUESTS = 4


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding files by name
# ---------------------------------------------------------------------------

def find(root: str, kind: str, name: str) -> str:
    """Path of ``kind`` file ``name`` under benchmark directory ``root``:
    kind is "configs", "traffic", "layer_metrics", "models" or
    "references"."""
    path = os.path.join(root, kind, name + _ext(kind))
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} file named {name!r} under {root} "
                       f"(have: {names(root, kind)})")
    return path


def names(root: str, kind: str) -> str:
    """The names of the ``kind`` files under ``root``, for a refusal."""
    ext, d = _ext(kind), os.path.join(root, kind)
    have = sorted(f[:-len(ext)] for f in os.listdir(d)
                  if f.endswith(ext)) if os.path.isdir(d) else []
    return ", ".join(have) or "none"


def _ext(kind: str) -> str:
    return ".json" if kind in ("configs", "traffic") else ".py"


def load_module(path: str):
    """Import the file ``path`` as a module of its own, registered under
    its directory's and its own name (dataclasses look their module up)."""
    kind, base = os.path.split(os.path.splitext(path)[0])
    name = "bench_{}_{}".format(os.path.basename(kind),
                                base.replace(".", "_"))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    readers: Dict[str, Callable]
    module: object          # the configuration's model module
    reference: object


def load_cell(benchmark: Dict, workload: str, root: str = BENCH) -> Cell:
    """The cell ``workload`` of ``benchmark`` (BENCHMARK.json's content),
    with its files found under ``root``."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have: "
                       f"{', '.join(sorted(cells))})")
    w = cells[workload]
    confs = {c["name"]: c for c in benchmark["configs"]}
    if w["config"] not in confs:
        raise KeyError(f"workload {workload!r} names unknown config "
                       f"{w['config']!r}")
    conf = load_json(find(root, "configs", w["config"]))
    if conf.get("name") != w["config"]:
        raise ValueError(f"config file of {w['config']!r} is named "
                         f"{conf.get('name')!r}")

    if "model" not in conf:
        raise KeyError(f"config {w['config']!r} names no model module "
                       f"(have: {names(root, 'models')})")

    def mine(m):
        return workload in m.get("workloads", [workload])
    per_layer = [m for m in benchmark["per_layer"] if mine(m)]
    return Cell(name=workload, chips=int(w["chips"]), conf=conf,
                traffic=load_json(find(root, "traffic", w["traffic"])),
                end_to_end=[m for m in benchmark["end_to_end"] if mine(m)],
                per_layer=per_layer,
                readers={m["name"]: load_module(find(
                    root, "layer_metrics", m["name"])).read
                    for m in per_layer},
                module=load_module(find(root, "models", conf["model"])),
                reference=load_module(find(root, "references",
                                           conf["reference"])))


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def engine_config(traffic: Dict):
    from repro.engine import EngineConfig
    return EngineConfig(num_slots=int(traffic["slots"]),
                        max_seq=int(traffic["max_seq"]),
                        page_size=int(traffic["page_size"]))


def _bucket(n: int, floor: int) -> int:
    """The power-of-two bucket ``floor * 2^i`` that a size ``n`` rounds
    up to, as the engine pads prompts and clamps block tables."""
    b = floor
    while b < n:
        b *= 2
    return b


def shapes(traffic: Dict, ecfg) -> Dict[str, List[int]]:
    """The prefill and decode shapes the window reaches once its first
    fill is in: a refill pads to the bucket of the longest prompt
    admitted with it (from ``prompt_bucket_min``, capped at max_seq); a
    decode step clamps its block tables to the bucket of the most pages
    a live request reserves (prompt plus budget, capped at the pages of
    max_seq), which is at least the smallest request's. The first fill's
    own prefill runs before the window opens and needs no warm-up; its
    bucket is listed under ``first_fill``."""
    page, top = ecfg.page_size, ecfg.max_seq
    block = int(traffic["block"])
    prompts = traffic_mod.quantiles(traffic["prompt"], block)
    budgets = traffic_mod.quantiles(traffic["output"], block)
    prefill = sorted({min(_bucket(int(p), ecfg.prompt_bucket_min), top)
                      for p in prompts})
    cap = -(-top // page)
    fill = traffic_mod.first_fill(traffic)
    least = min(int(prompts.min() + budgets.min()),
                min(f["prompt"] + f["budget"] for f in fill))
    b, decode = _bucket(-(-least // page), 1), []
    while True:
        decode.append(min(b, cap))
        if b >= cap:
            break
        b *= 2
    longest = max(f["prompt"] + f["done"] for f in fill)
    return {"prefill": prefill, "decode": decode, "first_fill": [
        min(_bucket(longest, ecfg.prompt_bucket_min), top)]}


def warm_groups(sh: Dict[str, List[int]], ecfg) -> List[List]:
    """Requests that take the engine through every shape in ``sh``, one
    group per scheduling boundary. For each decode bucket, in rising
    order, a long request reserves exactly that many pages and stays
    live, so each step runs at that bucket; short requests (two tokens)
    ride beside it, one per prefill bucket, each prefilled alone. The
    engine's first prefill and first decode step meet its initial,
    uncommitted state, which jit keys apart, so the first phase's first
    prefill shape comes again once the state is the steps' own."""
    page, top = ecfg.page_size, ecfg.max_seq
    s0 = sh["prefill"][0]

    def req(plen, max_new):
        return traffic_mod.Request(-1, np.zeros((min(plen, top - max_new),),
                                                np.int32), max_new, False)
    groups, pending = [], list(sh["prefill"])
    for d in sh["decode"]:
        hold = min(d * page, top)
        groups.append([req(s0, hold - s0), req(s0, 2)])
        fits = [s for s in pending if -(-(s + 2) // page) <= d]
        pending = [s for s in pending if s not in fits]
        groups += [[req(s, 2)] for s in fits]
    if pending:
        raise ValueError(f"prefill buckets {pending} reserve more pages "
                         f"than the largest decode bucket")
    return groups


def warm_up(engine, traffic: Dict) -> Dict[str, List[int]]:
    """Compile (or load from the persistent cache) exactly the prefill
    and decode programs the window reaches, by serving
    :func:`warm_groups` through ``InferenceEngine.run``; the engine is
    then the one the window runs on."""
    sh = shapes(traffic, engine.ecfg)
    engine.run(source=traffic_mod.Script(warm_groups(sh, engine.ecfg)))
    return sh


class _CompileLog(logging.Handler):
    """Names of the programs compiled while it is attached."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.names: List[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" ")[1])


# ---------------------------------------------------------------------------
# what the window measured
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """One request the window submitted, and what the engine served it."""
    rid: int
    prompt: np.ndarray
    max_new: int
    admitted: bool
    first_fill: bool
    tokens: Optional[np.ndarray]

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def produced(self) -> int:
        return 0 if self.tokens is None else int(self.tokens.shape[0])


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes: List[str] = []

    def note(self, msg: str) -> None:
        self.notes.append(msg)


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc)."""
    with open("/proc/self/stat") as f:
        start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def peaks_for(kind: str, path: Optional[str] = None) -> Dict:
    table = load_json(path or os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} has no peaks in peaks.json "
                       f"(have: {', '.join(sorted(table['devices']))})")
    return table["devices"][kind]


@dataclasses.dataclass
class Server:
    """A cell's model set up on its device: weights made, the engine
    built and taken through every shape its window reaches."""
    cell: Cell
    dev: object
    n_devices: int
    peaks: Optional[Dict]
    engine: object
    model: object           # the model module's Model.from_conf(conf)
    comp: Compression
    parts: Dict[str, float]
    compiles: List[int]


@dataclasses.dataclass
class Window:
    """What one measured window served and counted."""
    served: List[Served]
    tokens: int
    window_s: float
    setup_s: float
    peak_bytes: int
    counters: Dict[str, float]     # decode_steps, decode_tokens, registry's
    trace_dir: Optional[str]


def setup(cell: Cell, trace: bool = False, require_chip: bool = True,
          cache_root: Optional[str] = None, workers: Optional[int] = None,
          root: str = BENCH) -> Server:
    """Make the weights, place them, build the engine (with the
    program's tracing on when ``trace``) and warm up the cell's
    shapes."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"JAX reports {len(devices)} {dev.platform} device(s); "
                     f"the cell needs {cell.chips} TPU chip(s)")
    if cell.traffic.get("sampling") != "greedy":
        raise ValueError("the check compares greedy tokens: traffic must "
                         "sample greedily")
    peaks = peaks_for(dev.device_kind, os.path.join(root, "peaks.json")) \
        if require_chip else None
    if cache_root is None:
        cache_root = os.path.join(root, ".cache")
    if require_chip:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(cache_root, "jax"))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    workers = workers if workers is not None else \
        max(1, min(6, (os.cpu_count() or 2) - 1))
    from repro.engine import InferenceEngine, Telemetry
    compiles = [0]

    def on_event(event, *_a, **_k):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    parts: Dict[str, float] = {}
    t = time.perf_counter()
    params, wparts = cell.module.build_params(
        cell.conf, os.path.join(cache_root, "weights"), dev, workers)
    parts.update(wparts)
    parts["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = InferenceEngine(cell.module.program_config(cell.conf), params,
                             engine_config(cell.traffic),
                             telemetry=Telemetry(trace=trace))
    del params
    sh = warm_up(engine, cell.traffic)
    parts["warm_up_s"] = time.perf_counter() - t
    log(f"warm-up shapes: prefill buckets {sh['prefill']}, decode "
        f"max_live buckets {sh['decode']}")
    # what set-up left behind stays out of the collector's full passes
    # inside the window
    gc.collect()
    gc.freeze()
    return Server(cell=cell, dev=dev, n_devices=len(devices), peaks=peaks,
                  engine=engine, model=cell.module.Model.from_conf(cell.conf),
                  comp=Compression.from_conf(cell.conf["compression"]),
                  parts=parts, compiles=compiles)


def measure(srv: Server, seed: int, seconds: float, trace: bool) -> Window:
    """One measured window: the backlog of ``seed`` through
    ``InferenceEngine.run`` on the set-up engine. The run first prefills
    the first fill; the window opens when that prefill has returned its
    tokens (the device then holds no other work). At the first
    scheduling boundary ``seconds`` later nothing more is sent, every
    array the work sent so far computes is waited for, and the window
    closes; ``run`` then returns every token, outside the window.
    Traced when ``trace``."""
    import jax
    engine = srv.engine
    metrics = engine.metrics
    requests = traffic_mod.generate(srv.cell.traffic, seed, srv.model.vocab)
    base = len(metrics.requests)          # rid of the window's first request
    steps0, tokens0 = metrics.decode_steps, metrics.decode_tokens
    clog = _CompileLog()
    mark: Dict = {}

    def started() -> Optional[float]:
        rt = metrics.requests.get(base)
        return rt.first_token_t if rt is not None and rt.first_token_t > 0 \
            else None

    def on_start() -> None:
        mark["setup_s"] = process_age_s() - (metrics.now() - source.start)
        mark["compiles"], mark["logged"] = srv.compiles[0], len(clog.names)
        mark["registry"] = engine.tel.registry.window()
        if trace:
            # a TraceAnnotation starts when it is made, not when entered
            mark["annotation"] = jax.profiler.TraceAnnotation(WINDOW)
            mark["annotation"].__enter__()

    def on_stop() -> None:
        jax.block_until_ready(jax.live_arrays())
        mark["counted"] = mark["registry"].tick()[1]
        mark["compiled"] = srv.compiles[0] - mark["compiles"]
        mark["inside"] = clog.names[mark["logged"]:]
        if "annotation" in mark:
            mark.pop("annotation").__exit__(None, None, None)
    source = traffic_mod.Backlog(requests, seconds, started, metrics.now,
                                 on_start, on_stop)
    jax_log = logging.getLogger("jax")
    saved = (jax_log.handlers[:], jax_log.propagate)
    jax_log.handlers, jax_log.propagate = [clog], False
    jax.config.update("jax_log_compiles", True)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    t_run = metrics.now()
    try:
        out = engine.run(source=source)
        t_back = metrics.now()
    finally:
        if trace:
            if "annotation" in mark:
                mark.pop("annotation").__exit__(None, None, None)
            jax.profiler.stop_trace()
        jax.config.update("jax_log_compiles", False)
        jax_log.handlers, jax_log.propagate = saved
    if source.start is None:
        raise RuntimeError("the window never opened: the first fill got "
                           "no first token")
    if source.stopped_at is None:
        raise RuntimeError("the engine stopped before the window closed")
    stats = srv.dev.memory_stats() or {}
    served = _served(metrics, source, out, base)
    tokens = sum(s.produced for s in served) \
        - sum(1 for s in served if s.first_fill)
    log("setup: " + ", ".join(f"{k} {v:.3f}" for k, v in srv.parts.items())
        + f", first fill {source.start - t_run:.3f}, total to window "
        f"start {mark['setup_s']:.3f}s (process start)")
    inside = mark["inside"]
    log(f"window: {source.stopped_at - source.start:.3f}s; compilations "
        f"inside it: {mark['compiled']}"
        + (f" ({', '.join(inside)})" if inside else "")
        + f"; returning the tokens took "
        f"{t_back - source.stopped_at:.3f}s more")
    log(f"served: {len(served)} submitted, "
        f"{sum(s.admitted for s in served)} admitted, "
        f"{sum(s.admitted and not s.first_fill for s in served)} refills, "
        f"{tokens} tokens in the window")
    if all(s.admitted for s in served):
        log("the queue ran dry inside the window")
    return Window(served=served, tokens=tokens,
                  window_s=source.stopped_at - source.start,
                  setup_s=mark["setup_s"],
                  peak_bytes=int(stats.get("peak_bytes_in_use", 0)),
                  counters={"decode_steps": metrics.decode_steps - steps0,
                            "decode_tokens": metrics.decode_tokens - tokens0,
                            **mark["counted"]},
                  trace_dir=trace_dir)


def per_layer(srv: Server, win: Window) -> Tuple[Dict, Dict, Dict]:
    """The cell's per-layer metrics from a traced window, the device's
    busy and window seconds, and the breakdown."""
    import shutil
    t = time.perf_counter()
    tr_data = Trace.load(win.trace_dir)
    shutil.rmtree(win.trace_dir, ignore_errors=True)
    log(f"trace: {sum(len(d['ops']) for d in tr_data.devices.values())} "
        f"device ops, {len(tr_data.host)} host events, read in "
        f"{time.perf_counter() - t:.1f}s")
    span = tr_data.span(WINDOW)
    peaks = srv.peaks or {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    ctx = Context(trace=tr_data, window=span, window_ns=span[1] - span[0],
                  requests=win.served, model=srv.model, comp=srv.comp,
                  slots=srv.cell.traffic["slots"],
                  peak_flops=peaks["bf16_flops_per_s"],
                  peak_bw=peaks["hbm_bytes_per_s"],
                  decode_context_tokens=_decode_context(win.served),
                  counters=win.counters,
                  decode_steps=win.counters["decode_steps"],
                  decode_tokens=win.counters["decode_tokens"])
    metrics = {}
    for m in srv.cell.per_layer:
        v = srv.cell.readers[m["name"]](ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    for n in ctx.notes:
        log(n)
    device = {"busy_s": tr_data.busy_ns(span) * 1e-9,
              "window_s": (span[1] - span[0]) * 1e-9}
    kernels = KERNELS + tuple(getattr(srv.cell.module, "KERNELS", ()))
    breakdown = {"device_ops": tr_data.top_ops(span, kernels),
                 "idle_gaps": tr_data.idle_gaps(span, HOST_LABELS)}
    log(f"trace reduced in {time.perf_counter() - t:.1f}s")
    return metrics, device, breakdown


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, cache_root: Optional[str] = None,
        workers: Optional[int] = None, root: str = BENCH) -> Dict:
    """One run of ``cell``: set up, warm up, measure for ``seconds``,
    check. Returns the result line as a dict."""
    srv = setup(cell, trace, require_chip, cache_root, workers, root)
    win = measure(srv, seed, seconds, trace)
    # the program's state goes before the reference runs on the device
    srv.engine = None
    gc.unfreeze()
    gc.collect()
    attempted = [s for s in win.served if s.admitted]
    result: Dict = {"correct": False, "attempted": len(attempted),
                    "failed": sum(1 for s in attempted if s.produced == 0)}
    device = {"platform": srv.dev.platform, "kind": srv.dev.device_kind,
              "count": srv.n_devices, "memory_peak_bytes": win.peak_bytes}
    if trace:
        metrics, busy, result["breakdown"] = per_layer(srv, win)
        device.update(busy)
    else:
        metrics = end_to_end(cell, win)
    result["metrics"] = metrics
    result["device"] = device
    check = check_outputs(cell, win.served, seed, srv.model)
    result["correct"] = check.pop("ok")
    result["check"] = check
    return result


def _served(metrics, source, out, base: int) -> List[Served]:
    toks = {r["rid"]: np.asarray(r["tokens"]) for r in out["results"]
            if r["rid"] >= base}
    return [Served(rid=base + g.idx, prompt=g.prompt, max_new=g.max_new,
                   admitted=metrics.requests[base + g.idx].admit_t > 0,
                   first_fill=g.first_fill, tokens=toks.get(base + g.idx))
            for g in source.submitted]


def _decode_context(served: List[Served]) -> float:
    """Keys attended by every decode token served: the j-th decode step
    of a request with a P-token prompt (j = 1 .. produced - 1) attends to
    P + j positions."""
    tot = 0.0
    for s in served:
        d = s.produced - 1
        if d > 0:
            tot += d * s.prompt_len + d * (d + 1) / 2
    return tot


def end_to_end(cell: Cell, win: Window) -> Dict:
    values = {"setup_s": win.setup_s,
              "output_tok_s": win.tokens / win.window_s}
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def compare(cell: Cell, served: List[Served], seed: int,
            control: bool = False) -> Dict[str, np.ndarray]:
    """Run the plain reference over a sample of the served requests (the
    one with the most served tokens, and others drawn from the seed):
    ``gap`` is how far each served token's reference logit lies below
    the reference's best. With ``control``, ``control_gap`` is the same
    reading for the token the float8 control puts first at each of
    those positions: the control in the program's place."""
    done = [s for s in served if s.produced > 0]
    if not done:
        inf = np.array([np.inf])
        return {"gap": inf, "control_gap": inf} if control else {"gap": inf}
    rng = np.random.default_rng([seed, 1])
    pick = cell.reference.check_sample([s.produced for s in done], rng,
                                       CHECK_REQUESTS)
    sample = [done[i] for i in pick]
    t = time.perf_counter()
    gaps = cell.reference.served_gaps(
        cell.conf, [s.prompt for s in sample], [s.tokens for s in sample],
        int(cell.traffic["max_seq"]), CHECK_REQUESTS, control=control)
    log(f"reference: {len(sample)} requests, {gaps['gap'].shape[0]} "
        f"served tokens compared in {time.perf_counter() - t:.1f}s")
    return gaps


def decide(cell: Cell, served: List[Served], model, gap: float,
           label: str = "check") -> Dict:
    """Every number compared, each with its limit, and ``ok``: whether
    all hold. ``gap`` is the widest logit gap of the tokens judged."""
    done = [s for s in served if s.produced > 0]
    bad = [s.rid for s in done
           if s.produced > s.max_new or s.tokens.min() < 0
           or s.tokens.max() >= model.vocab]
    unstarted = sum(1 for s in served if s.admitted and s.produced == 0)
    out = {"malformed_requests": {"value": len(bad), "limit": 0},
           "requests_without_tokens": {"value": unstarted, "limit": 0},
           "max_logit_gap": {"value": float(gap), "limit": float(
               cell.conf["check"]["max_logit_gap"])}}
    out["ok"] = bool(not bad and unstarted == 0
                     and gap <= out["max_logit_gap"]["limit"])
    for k, v in out.items():
        if isinstance(v, dict):
            log(f"{label} {k}: {v['value']} (limit {v['limit']})")
    return out


def check_outputs(cell: Cell, served: List[Served], seed: int,
                  model) -> Dict:
    """The decision on what the window served: :func:`decide` on the
    served tokens' widest gap."""
    gaps = compare(cell, served, seed)
    return decide(cell, served, model, float(gaps["gap"].max()))
