"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics and the ``breakdown`` read.

Read with ``jax.profiler.ProfileData``. A device plane is one whose name
starts with ``/device:TPU:``; on it, the ``XLA Modules`` line holds one
event per executed program (its name carries the jitted function's name)
and the ``XLA Ops`` line one event per executed operation. Host
annotations (``jax.profiler.TraceAnnotation``) sit on the lines of the
``/host:CPU`` plane, on the same clock.

* busy: the union of op intervals on a device plane, inside the window;
  averaged over the device planes that ran anything;
* per-kernel time: ops whose name or string stats name the kernel (the
  ``name=`` a Pallas kernel is given), optionally only those inside the
  programs of one jitted function;
* per-program time: module events by the jitted function's name;
* idle gaps: the stretches of the window with no op on the device,
  labelled by the innermost host annotation that overlaps each most.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES, OPS = "XLA Modules", "XLA Ops"

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class Event:
    __slots__ = ("name", "start", "end", "text")

    def __init__(self, name: str, start: float, end: float, text: str):
        self.name, self.start, self.end, self.text = name, start, end, text


def _events(line, with_text: bool = False) -> List[Event]:
    """The line's events; ``with_text`` keeps their string stats, where a
    kernel's name may sit."""
    out = []
    for e in line.events:
        text = " ".join(str(v) for _, v in e.stats
                        if isinstance(v, str)) if with_text else ""
        out.append(Event(e.name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns), text))
    return out


class Trace:
    """The parts of one trace the metrics read. Times are nanoseconds on
    the trace's clock."""

    def __init__(self, devices: Dict[str, Dict[str, List[Event]]],
                 host: List[Event]):
        self.devices = devices          # plane -> {"modules", "ops"}
        self.host = host
        self._modules: Dict[str, List[Event]] = {}

    @classmethod
    def load(cls, path: str) -> "Trace":
        """``path``: an ``.xplane.pb`` file (gzipped if it ends in
        ``.gz``) or a directory holding one."""
        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**",
                                                  "*.xplane.pb"),
                                     recursive=True))
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        from jax.profiler import ProfileData
        if path.endswith(".gz"):
            with gzip.open(path, "rb") as f:
                pd = ProfileData.from_serialized_xspace(f.read())
        else:
            pd = ProfileData.from_file(path)
        devices, host = {}, []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                lines = {l.name: l for l in plane.lines}
                devices[plane.name] = {
                    "modules": _events(lines[MODULES])
                    if MODULES in lines else [],
                    "ops": _events(lines[OPS], with_text=True)
                    if OPS in lines else []}
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    host += _events(line)
        return cls(devices, host)

    # -- the window -------------------------------------------------------

    def span(self, name: str) -> Optional[Interval]:
        """The first host annotation called ``name``."""
        for e in self.host:
            if e.name == name:
                return e.start, e.end
        return None

    def _active(self) -> List[Dict[str, List[Event]]]:
        return [d for d in self.devices.values() if d["ops"]]

    def busy_ns(self, window: Interval) -> float:
        """Union of op time inside ``window``, averaged over the device
        planes that ran an op."""
        devs = self._active()
        if not devs:
            return 0.0
        tot = 0.0
        for d in devs:
            iv = _clip(union((e.start, e.end) for e in d["ops"]), *window)
            tot += sum(e - s for s, e in iv)
        return tot / len(devs)

    # -- programs and kernels ---------------------------------------------

    def modules(self, fn: str, window: Interval) -> List[Event]:
        """Executions of the programs of jitted function ``fn`` (module
        names read ``jit_<fn>``, with a suffix after a non-word
        character)."""
        if fn not in self._modules:
            pat = re.compile(rf"(^|[^A-Za-z0-9_]|jit_){re.escape(fn)}"
                             r"([^A-Za-z0-9_]|$)")
            self._modules[fn] = [e for d in self._active()
                                 for e in d["modules"] if pat.search(e.name)]
        return [e for e in self._modules[fn]
                if e.end > window[0] and e.start < window[1]]

    def module_ns(self, fn: str, window: Interval) -> float:
        return sum(e.end - e.start for e in self.modules(fn, window))

    def kernel_ns(self, kernel: str, window: Interval,
                  within: Optional[str] = None) -> float:
        """Summed device time of the ops that name ``kernel``; with
        ``within``, only those inside a program of that jitted function."""
        spans = None
        if within is not None:
            spans = union((e.start, e.end)
                          for e in self.modules(within, window))
        tot = 0.0
        for d in self._active():
            for e in d["ops"]:
                if not (e.end > window[0] and e.start < window[1]):
                    continue
                if kernel not in e.name and kernel not in e.text:
                    continue
                if spans is not None and not _inside(e, spans):
                    continue
                tot += e.end - e.start
        return tot / len(self._active()) if self._active() else 0.0

    # -- breakdown ----------------------------------------------------------

    def top_ops(self, window: Interval, kernels: Iterable[str],
                n: int = 10) -> List[List]:
        """[name, seconds] of the leaf ops that took most device time
        (an op that encloses others, such as a ``while`` around a layer
        scan, is left out so no time counts twice): ops of a named
        kernel under its name, others by HLO instruction name without
        its numeric suffix."""
        kernels = list(kernels)
        tot: Dict[str, float] = defaultdict(float)
        for d in self._active():
            for e in _leaves(d["ops"]):
                if not (e.end > window[0] and e.start < window[1]):
                    continue
                key = next((k for k in kernels
                            if k in e.name or k in e.text), None)
                tot[key or op_name(e.name)] += (e.end - e.start) * 1e-9
        ndev = max(1, len(self._active()))
        return [[k, v / ndev] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, window: Interval, labels: Iterable[str],
                  n: int = 10) -> List[List]:
        """[host label, seconds] of device idle time inside ``window``,
        summed by the host annotation (among ``labels``) that overlaps
        each gap most; "host:other" where none does. The labelled
        annotations come from one host thread, so they do not overlap
        and a gap's candidates are found by bisection."""
        labels = set(labels)
        host = sorted((e for e in self.host if e.name in labels),
                      key=lambda e: e.start)
        ends = [e.end for e in host]
        tot: Dict[str, float] = defaultdict(float)
        devs = self._active()
        for d in devs:
            busy = _clip(union((e.start, e.end) for e in d["ops"]), *window)
            edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
            for s, e in zip(edges[::2], edges[1::2]):
                if e <= s:
                    continue
                best, over = "host:other", 0.0
                i = bisect.bisect_right(ends, s)
                while i < len(host) and host[i].start < e:
                    o = min(e, host[i].end) - max(s, host[i].start)
                    if o > over:
                        best, over = "host:" + host[i].name, o
                    i += 1
                tot[best] += (e - s) * 1e-9
        ndev = max(1, len(devs))
        return [[k, v / ndev] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.:]\d+$", "", head)


def _leaves(ops: List[Event]) -> List[Event]:
    """The ops that enclose no other op."""
    order = sorted(ops, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(order, order[1:] + [None])
            if nxt is None or nxt.start >= e.end]


def _inside(e: Event, spans: List[Interval]) -> bool:
    lo, hi = 0, len(spans)
    while lo < hi:                      # last span starting at or before e
        mid = (lo + hi) // 2
        if spans[mid][0] <= e.start:
            lo = mid + 1
        else:
            hi = mid
    return lo > 0 and e.end <= spans[lo - 1][1] + 1.0
