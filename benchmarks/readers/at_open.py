"""What the program's own series read at the window's FIRST edge: set-up,
as the program timed it from inside. ``run["edges"].start`` is read
(``Edges.open()``) at the very instant ``setup_s`` is stamped, so a sum of
start-up phases taken there and ``setup_s`` cover the same stretch.

``counters`` are summed; a series among them that no registry has makes the
metric None (a program from before the series, as a parent commit may be:
the metric is left out of the line). ``optional`` are summed too where they
exist (a series only one runner's cells have). With ``den`` the sum is
divided by the sum of those (None where that is 0 or missing); with
``one_minus`` the ratio's complement is taken; with ``remainder_of`` the
result is ``run[<that key>]`` minus the sum: what no series covers. All
times ``scale``.
"""

from lib import common


def wants(spec: dict) -> list:
    return [w for k in ("counters", "optional", "den")
            for w in spec.get(k, [])]


def _sum_at_open(run: dict, series: list, missing=None):
    """The sum of ``series`` at the window's first edge; ``missing`` stands
    for one that is not there, and None for it makes the sum None."""
    start = run["edges"].start
    vals = [start.get(common.Edges.key(w)) for w in series]
    vals = [missing if v is None else v for v in vals]
    return None if any(v is None for v in vals) else float(sum(vals))


def read(spec: dict, run: dict):
    value = _sum_at_open(run, spec["counters"])
    if value is None:
        return None
    value += _sum_at_open(run, spec.get("optional", []), missing=0.0)
    if "den" in spec:
        den = _sum_at_open(run, spec["den"])
        if not den:
            return None
        value /= den
    if spec.get("one_minus"):
        value = 1.0 - value
    if "remainder_of" in spec:
        whole = run.get(spec["remainder_of"])
        if whole is None:
            return None
        value = whole - value
    return spec.get("scale", 1.0) * value
