"""From a profiler trace (``.xplane.pb``) to busy time, operation times and
named idle gaps. Nothing but JAX is needed to read one
(``jax.profiler.ProfileData``).

What a TPU trace holds (looked at by hand, PR 24): one plane for each chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event for each
executed HLO instruction (name: the instruction's text, ``%fusion.2 = ...``)
and whose line ``XLA Modules`` carries one for each executed program
(``jit_step(<fingerprint>)``); ``Async XLA Ops`` repeats copies that overlap
compute and is not counted as busy. The plane ``/host:CPU`` has a line for
each host thread with the program's ``TraceAnnotation`` spans and JAX's own
(``PjitFunction(step)``). Host and device lines share one clock to within
about a millisecond.

The reduction works on plain tuples so that a recorded event list can stand
in for a trace in tests: ``events`` are ``(name, start_ns, duration_ns)``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]

_OP = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?\s*=")


def op_name(text: str) -> str:
    """A stable short name for an ``XLA Ops`` event: the instruction's name
    without its ``%`` and numeric suffix; a Mosaic (Pallas) kernel, which
    XLA runs as a custom call to ``tpu_custom_call``, gets that target
    appended so that kernels can be told from XLA's own fusions."""
    m = _OP.match(text)
    name = m.group(1) if m else text.split("(")[0].strip()[:64]
    if 'custom_call_target="tpu_custom_call"' in text:
        name += ":tpu_custom_call"
    return name


def load(path: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``path`` (or the file itself)
    into ``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    {thread: [...]}}`` of ``(name, start_ns, duration_ns)`` tuples."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:     # two threads can share a name
                out["host"].setdefault(line.name, []).extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
    return out


def union_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi)
    total, end = 0.0, lo
    for s, e in spans:
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def gaps(events: Iterable[Event], lo: float, hi: float,
         min_ns: float = 0.0) -> List[Tuple[float, float]]:
    """The idle intervals (start, end) inside [lo, hi] between the events'
    union, the stretches before the first and after the last included."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi)
    out, end = [], lo
    for s, e in spans:
        if s - end > min_ns:
            out.append((end, s))
        end = max(end, e)
    if hi - end > min_ns:
        out.append((end, hi))
    return out


def find_span(trace: dict, name: str) -> Optional[Tuple[float, float]]:
    """(start, end) of the first host span called ``name``."""
    for events in trace["host"].values():
        for n, s, d in events:
            if n == name:
                return s, s + d
    return None


class HostSpans:
    """The host's spans as arrays, so that naming a gap is a few vector
    operations and not a walk over every span. Python frames (names that
    start with ``$``) and the names in ``ignore`` are left out."""

    def __init__(self, trace: dict, ignore: Tuple[str, ...] = ()):
        import numpy as np
        names, ids, starts, ends = {}, [], [], []
        for events in trace["host"].values():
            for n, s, d in events:
                if n.startswith("$") or n in ignore:
                    continue
                ids.append(names.setdefault(n, len(names)))
                starts.append(s)
                ends.append(s + d)
        self.names = list(names)
        self.ids = np.asarray(ids, np.int64)
        self.starts = np.asarray(starts, np.float64)
        self.ends = np.asarray(ends, np.float64)

    def activity(self, lo: float, hi: float) -> str:
        """What the host was doing in [lo, hi]: the span name (the
        program's or the benchmark's annotations, JAX's dispatch spans)
        that covers at least half of the interval; where several do, the
        one with the least cover, which is the most specific; ``host_idle``
        when none does."""
        import numpy as np
        if not self.names:
            return "host_idle"
        overlap = np.clip(np.minimum(self.ends, hi)
                          - np.maximum(self.starts, lo), 0.0, None)
        cover = np.bincount(self.ids, weights=overlap,
                            minlength=len(self.names))
        half = [(c, n) for c, n in zip(cover.tolist(), self.names)
                if c >= 0.5 * (hi - lo) and c > 0]
        return min(half)[1] if half else "host_idle"


NAMED_GAPS = 512      # the longest are named; the seams left over are lumped


def reduce(trace: dict, window: Optional[Tuple[float, float]] = None,
           ignore: Tuple[str, ...] = ()) -> dict:
    """Busy and idle seconds, operation times and named gaps.

    ``window`` is (start_ns, end_ns) on the trace's clock; without one it
    runs from the first device operation to the end of the last. Returns
    ``busy_s`` (mean over the chips of the union of their operations),
    ``window_s``, ``op_seconds`` (short name -> seconds, mean over chips),
    ``op_calls``, and ``idle_gaps`` (host activity -> seconds, chip 0; the
    ``NAMED_GAPS`` longest gaps are named, the rest are ``short_seams``).
    """
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    if window is None:
        ops = [e for dev in devices.values() for e in dev["ops"]]
        if not ops:
            raise ValueError("no operation ran on the device in this trace")
        window = (min(s for _, s, _ in ops), max(s + d for _, s, d in ops))
    lo, hi = window
    n = len(devices)
    busy = 0.0
    op_seconds: Dict[str, float] = {}
    op_calls: Dict[str, int] = {}
    short: Dict[str, str] = {}
    for dev in devices.values():
        busy += union_ns(dev["ops"], lo, hi)
        for text, s, d in dev["ops"]:
            if s < lo or s + d > hi:
                continue        # cut by an edge: in busy, not in the table
            name = short.get(text) or short.setdefault(text, op_name(text))
            op_seconds[name] = op_seconds.get(name, 0.0) + d / 1e9 / n
            op_calls[name] = op_calls.get(name, 0) + 1
    first = devices[sorted(devices)[0]]
    idle: Dict[str, float] = {}
    spans = HostSpans(trace, ignore)
    by_length = sorted(gaps(first["ops"], lo, hi), key=lambda g: g[0] - g[1])
    for i, (s, e) in enumerate(by_length):
        what = spans.activity(s, e) if i < NAMED_GAPS else "short_seams"
        idle[what] = idle.get(what, 0.0) + (e - s) / 1e9
    return {"busy_s": busy / n / 1e9, "window_s": (hi - lo) / 1e9,
            "op_seconds": op_seconds, "op_calls": op_calls,
            "idle_gaps": idle, "chips": n}


def seconds_matching(reduced: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["op_seconds"].items() if rx.search(k))


def calls_matching(reduced: dict, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["op_calls"].items() if rx.search(k))


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the operations that took most
    device time and the longest idle gaps by what the host was doing."""
    def first(d):
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:top]]
    return {"device_ops": first(reduced["op_seconds"]),
            "idle_gaps": first(reduced["idle_gaps"])}
