"""Readers of the per-layer metrics, chosen by ``reader.kind`` in each
metric's file (``metrics/<name>.json``). A later PR adds a metric by adding
a file. Where no kind below fits, the kind is a file of its own,
``readers/<kind>.py``, with ``read(spec, run)`` and, where it needs the
program's counters or gauges read around the window, ``wants(spec)`` and
``gauges(spec)``.

Every reader takes ``(spec, run)``: ``spec`` is the file's ``reader`` object
and ``run`` is what the cell's runner measured: ``window_s``, ``steps`` or
``requests``, ``edges`` (the program's counters at the window's two edges),
``gauge_peaks``, ``trace`` (the reduced trace, ``--trace 1`` only), ``cfg``,
``mix``, ``peaks``, ``chips``, ``memory_peak_bytes``, ``compiles_in_window``,
``flops`` (the analytic operations of the window) and ``family`` (the
configuration's family file, which a kernel's reader asks for the kernel's
shape, operations and bytes). A reader that finds nothing to read returns
None and the metric is left out of the line; none returns 0 for a share of
a roofline or of a peak.
"""

from __future__ import annotations

from typing import Optional

from . import common, ops_count, xplane


def _path(obj: dict, dotted: str):
    for part in dotted.split("."):
        obj = obj[part]
    return obj


def counters(spec: dict) -> list:
    """The counters that the kinds below read at the window's edges."""
    return [spec[k] for k in ("counter", "num", "den")
            if isinstance(spec.get(k), dict)]


def counter_over_window(spec, run) -> Optional[float]:
    d = run["edges"].delta(spec["counter"])
    return None if d is None else spec.get("scale", 1.0) * d / run["window_s"]


def counter_over_units(spec, run) -> Optional[float]:
    d, units = run["edges"].delta(spec["counter"]), run.get(spec["units"])
    return None if d is None or not units else d / units


def counter_ratio(spec, run) -> Optional[float]:
    num, den = run["edges"].delta(spec["num"]), run["edges"].delta(spec["den"])
    if num is None or not den:
        return None
    if "den_times" in spec:
        den *= float(_path(run["cfg"], spec["den_times"]))
    return spec.get("scale", 1.0) * num / den


def compiles(spec, run) -> Optional[float]:
    return float(run["compiles_in_window"])


def mfu(spec, run) -> Optional[float]:
    """The whole window's analytic operations over its length, the chips
    and the table's peak."""
    if not run.get("flops"):
        return None
    return 100.0 * run["flops"] / run["window_s"] / run["chips"] \
        / run["peaks"]["flops_bf16"]


def memory_peak_gib(spec, run) -> Optional[float]:
    peak = run.get("memory_peak_bytes")
    return None if not peak else peak / 2.0 ** 30


def device_idle(spec, run) -> Optional[float]:
    t = run.get("trace")
    return None if not t else 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def trace_share(spec, run) -> Optional[float]:
    """Device time of the operations matching ``pattern`` as a share of the
    device's busy time in the traced slice (the union of its operations:
    their sum would count a ``while`` and its body twice)."""
    t = run.get("trace")
    if not t:
        return None
    s = xplane.seconds_matching(t, spec["pattern"])
    return 100.0 * s / t["busy_s"] if s > 0 and t["busy_s"] > 0 else None


def flash_roofline(spec, run) -> Optional[float]:
    """The least time the chip could take for the causal-attention kernel
    calls seen whole in the traced slice (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s, per call) over the time they
    took. Forward calls do 2 matrix products over the causal triangle and
    move 4 tensors; each layer's backward does 4 and moves 8, spread over
    ``bwd_calls_per_layer`` calls."""
    t = run.get("trace")
    if not t:
        return None
    peaks = run["peaks"]
    shape = run["family"].attention_shape(run["cfg"], run["mix"])

    def least(products, tensors):
        return max(ops_count.causal_attention_flops(*shape, products)
                   / peaks["flops_bf16"],
                   ops_count.attention_tensor_bytes(*shape, tensors)
                   / peaks["hbm_bytes_per_s"])

    chips = t["chips"]
    fwd_calls = xplane.calls_matching(t, spec["fwd_pattern"]) / chips
    bwd_calls = xplane.calls_matching(t, spec["bwd_pattern"]) / chips
    took = (xplane.seconds_matching(t, spec["fwd_pattern"])
            + xplane.seconds_matching(t, spec["bwd_pattern"]))
    if took <= 0:
        return None
    need = (fwd_calls * least(2, 4)
            + bwd_calls / spec["bwd_calls_per_layer"] * least(4, 8))
    return 100.0 * need / took


def request_percentile(spec, run) -> Optional[float]:
    vals = [r[spec["field"]] for r in run.get("requests", [])
            if r.get(spec["field"]) is not None]
    if len(vals) < spec.get("min_samples", 2):
        return None
    return spec.get("scale", 1.0) * percentile(vals, spec["q"])


def request_ratio(spec, run) -> Optional[float]:
    reqs = run.get("requests", [])
    den = sum(r[spec["den"]] for r in reqs)
    return None if not den else \
        spec.get("scale", 1.0) * sum(r[spec["num"]] for r in reqs) / den


def gauge_peak_share(spec, run) -> Optional[float]:
    peak = run.get("gauge_peaks", {}).get(spec["gauge"])
    return None if peak is None else \
        100.0 * peak / float(_path(run["cfg"], spec["of"]))


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between the
    sorted values; the median of one value is that value."""
    v = sorted(values)
    if len(v) == 1:
        return float(v[0])
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


KINDS = {f.__name__: f for f in (
    counter_over_window, counter_over_units, counter_ratio, compiles, mfu,
    memory_peak_gib, device_idle, trace_share, flash_roofline,
    request_percentile, request_ratio, gauge_peak_share)}


def _file_of(spec: dict, dirs):
    """The reader file of a kind that is none of ``KINDS``."""
    return common.find_module(dirs, "readers", spec["kind"])


def wants(spec: dict, dirs=(common.BENCH,)) -> list:
    """The counters a metric's reader needs read at the window's edges."""
    if spec["kind"] in KINDS:
        return counters(spec)
    return getattr(_file_of(spec, dirs), "wants", counters)(spec)


def gauges(spec: dict, dirs=(common.BENCH,)) -> list:
    """The gauges a metric's reader needs sampled while the window is open."""
    if spec["kind"] in KINDS:
        return [spec["gauge"]] if spec["kind"] == "gauge_peak_share" else []
    return getattr(_file_of(spec, dirs), "gauges", lambda spec: [])(spec)


def read(metric_file: dict, run: dict,
         dirs=(common.BENCH,)) -> Optional[float]:
    spec = metric_file["reader"]
    fn = KINDS.get(spec["kind"]) or _file_of(spec, dirs).read
    return fn(spec, run)
