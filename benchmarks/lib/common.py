"""What every cell's runner needs: the clock of the process, the device
check, the compile cache, counters read at the window's two edges, and the
traced slice of the window."""

from __future__ import annotations

import importlib.util
import json
import os
import threading
import time
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


def process_age_s() -> float:
    """Seconds since this process was started (the kernel's own record), so
    that ``setup_s`` counts the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def find(dirs, kind: str, filename: str) -> str:
    """The path of ``<kind>/<filename>`` in the first of ``dirs``, each laid
    out like ``benchmarks/``, that has it; none fails with every path
    looked for."""
    paths = [os.path.join(d, kind, filename) for d in dirs]
    for path in paths:
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"benchmark: no {kind}/{filename}: looked for "
        + ", ".join(os.path.relpath(p, ROOT) for p in paths))


def load_module(path: str, name: str):
    """The Python file at ``path`` as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_module(dirs, kind: str, name: str):
    """``<kind>/<name>.py``: a family, or a reader of a kind of its own."""
    return load_module(find(dirs, kind, f"{name}.py"),
                       f"bench_{kind}_{name}")


def load_family(cfg: dict, dirs=(BENCH,)):
    """The family file that a configuration's ``model_type`` names."""
    return find_module(dirs, "families", cfg["model_type"])


def load_reference(cfg: dict):
    """The plain reference that a configuration names, beside it."""
    return load_module(os.path.join(ROOT, cfg["reference"]), "cell_reference")


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at one fixed path inside the checkout (the path is part of
    the cache's key). Every program is cached, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info(chips: int, rehearse: bool) -> dict:
    """The devices as JAX reports them; anything but ``chips`` TPU devices
    is fatal unless this is a CPU rehearsal, which asks for it by flag."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise SystemExit(f"benchmark: platform is {info['platform']!r}, not "
                         "'tpu': no accelerator, nothing was measured")
    if info["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {info['count']}")
    return info


class CompileCounter:
    """Counts XLA compilations (JAX's own monitoring event), whoever asks
    for them: the program, the benchmark or a retrace nobody meant."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


# -- registry counters at the window's edges --------------------------------

def read_stat(registries, name: str, labels: Optional[dict] = None,
              stat: str = "value") -> Optional[float]:
    """Sum of ``stat`` (``value`` of a counter or gauge; ``sum`` or
    ``count`` of a histogram) over the series of metric ``name`` whose
    labels include ``labels``, over every registry given. None when no
    registry has the metric."""
    total, found = 0.0, False
    for reg in registries:
        metric = reg.get(name)
        if metric is None:
            continue
        found = True
        for series in metric.snapshot()["series"]:
            if all(series["labels"].get(k) == v
                   for k, v in (labels or {}).items()):
                total += float(series[stat])
    return total if found else None


class Edges:
    """The program's counters read at the window's two edges. ``wants`` are
    the ``{"metric", "labels", "stat"}`` of every metric file the cell
    reports; ``delta(want)`` is end minus start."""

    def __init__(self, registries, wants: List[dict]):
        self.registries = registries
        self.wants = {self.key(w): w for w in wants}
        self.start: Dict[str, Optional[float]] = {}
        self.end: Dict[str, Optional[float]] = {}

    @staticmethod
    def key(w: dict) -> str:
        return json.dumps([w["metric"], w.get("labels") or {},
                           w.get("stat", "value")], sort_keys=True)

    def _read(self) -> Dict[str, Optional[float]]:
        return {k: read_stat(self.registries, w["metric"], w.get("labels"),
                             w.get("stat", "value"))
                for k, w in self.wants.items()}

    def open(self) -> None:
        self.start = self._read()

    def close(self) -> None:
        self.end = self._read()

    def delta(self, want: dict) -> Optional[float]:
        k = self.key(want)
        if self.end.get(k) is None:
            return None
        return self.end[k] - (self.start.get(k) or 0.0)


class GaugeSampler(threading.Thread):
    """Samples gauges every ``period_s`` while the window is open and keeps
    each one's largest reading."""

    def __init__(self, registries, names: List[str], period_s: float = 0.05):
        super().__init__(daemon=True, name="bench-gauges")
        self.registries, self.names, self.period_s = registries, names, period_s
        self.peak: Dict[str, float] = {}
        self._halt = threading.Event()

    def run(self) -> None:
        while self.names and not self._halt.is_set():
            for n in self.names:
                v = read_stat(self.registries, n)
                if v is not None:
                    self.peak[n] = max(self.peak.get(n, v), v)
            self._halt.wait(self.period_s)

    def stop(self) -> None:
        self._halt.set()
        self.join()


# -- the traced slice -------------------------------------------------------

WINDOW_SPAN = "bench.traced_window"


class TraceSlice(threading.Thread):
    """With ``--trace 1``: a thread that, ``after_s`` into the window, runs
    the profiler for ``length_s`` seconds under a host span of that name,
    which gives the reduction its window on the trace's own clock."""

    def __init__(self, directory: str, after_s: float, length_s: float):
        super().__init__(daemon=True, name="bench-trace")
        self.directory, self.after_s, self.length_s = (directory, after_s,
                                                       length_s)
        self.error: Optional[BaseException] = None

    def _mark(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            time.sleep(self.length_s)

    def run(self) -> None:
        import jax
        try:
            time.sleep(self.after_s)
            # no Python frames: with them a serving slice holds millions of
            # host events and the run spends minutes writing and reading it
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            try:
                # the span is held by a thread of its own: one made by the
                # thread that starts and stops the profiler can go missing
                marker = threading.Thread(target=self._mark,
                                          name="bench-trace-span")
                marker.start()
                marker.join()
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 - reported by the runner
            self.error = e


def trace_plan(seconds: float):
    """(after_s, length_s): a few seconds well inside the window."""
    length = min(3.0, max(0.5, seconds / 4.0))
    return max(0.2, min(5.0, seconds / 3.0)), length


LAST_MEMORY_STATS: dict = {}     # all the allocator said at that moment


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes held on the fullest chip, where the backend says: the
    buffers' peak (``peak_bytes_in_use``: parameters, state, caches) plus
    what the loaded programs reserve for their temporaries
    (``peak_bytes_reserved``; on this TPU runtime the allocator counts the
    two apart: PERF.md, Findings, PR 24)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    LAST_MEMORY_STATS.update(stats[0])
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in stats if "peak_bytes_in_use" in s]
    return int(max(peaks)) if peaks else None


def free_device_memory() -> None:
    """Collect what the caller has just let go of, so that the reference
    finds the memory the program held."""
    import gc
    gc.collect()
    import jax
    jax.clear_caches()
    gc.collect()
