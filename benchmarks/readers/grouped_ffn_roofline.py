"""The share of its roofline that the expert layers' grouped product (the
Pallas kernel ``ops/grouped_ffn``) reaches: the least time the chip could
take for the work the window routed to the held experts, over the time the
kernel's calls took.

The calls of a window differ (routing is uneven from step to step), so the
need is not a call's times the calls: the program counts what the window
routed (pairs, and held experts that got a pair at all) at its two edges,
the configuration's family turns that into operations and bytes (the
algorithm's: each touched expert's two matrices read once a layer and step,
a pair's row in and out; a tile's slack rows, a matrix read again for a
second tile and the absent experts count nothing), and both sides are taken
as rates: need a second of the window over kernel seconds a second of the
traced slice. The need is the larger of the window's operations over peak
FLOP/s and its bytes over peak bytes/s, which is at most the sum over calls
of each call's larger: the share errs low, never above what a call-by-call
count would give. A program without the counters or the kernel, as a parent
commit may be, gives nothing.
"""

from lib import xplane


def wants(spec: dict) -> list:
    return [spec["pairs"], spec["touched"]]


def read(spec: dict, run: dict):
    trace = run.get("trace")
    family = run["family"]
    if not trace or not hasattr(family, "grouped_ffn_work"):
        return None
    pairs = run["edges"].delta(spec["pairs"])
    touched = run["edges"].delta(spec["touched"])
    took = xplane.seconds_matching(trace, spec["pattern"])
    if not pairs or touched is None or took <= 0:
        return None
    ops, nbytes = family.grouped_ffn_work(run["cfg"], pairs, touched)
    peaks = run["peaks"]
    need = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return (100.0 * (need / run["window_s"] / run["chips"])
            / (took / trace["window_s"]))
