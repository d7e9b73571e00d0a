"""A gauge's largest reading while the window was open, times ``scale``
(``lib/readers.gauge_peak_share`` gives a peak as a share of a size the
configuration names; this gives it in a unit of its own, as bytes in GiB).
A program without the gauge, as a parent commit may be, gives nothing."""


def gauges(spec: dict) -> list:
    return [spec["gauge"]]


def read(spec: dict, run: dict):
    peak = run.get("gauge_peaks", {}).get(spec["gauge"])
    return None if peak is None else spec.get("scale", 1.0) * peak
