"""The share of its roofline that the gated expert layers' grouped product
(the Pallas kernel ``moe_gated_ffn`` of ``ops/grouped_ffn``) reaches, with
the need and the time taken over the SAME calls.

The calls of a window differ (routing is uneven from step to step), so the
program counts what the window routed (pairs, and held experts that got a
pair at all) and how many calls of the grouped product that was
(``moe_grouped_calls_total``: dispatches x expert layers x steps, counted on
the host); the configuration's family turns pairs and touched experts into
operations and bytes (the algorithm's: each touched expert's three matrices
read once a layer and step, a pair's row in and out; a tile's slack rows,
matrices read again for a second tile and the absent experts count
nothing). A mean call's need is the larger of its operations over peak
FLOP/s and its bytes over peak bytes/s; the traced slice holds so many of
the kernel's calls (``xplane.calls_matching``) and they took so long
(``seconds_matching``): need of those calls over their time. No rate of the
window is set against a rate of the slice, so a stall in one and not in the
other moves nothing (``readers/grouped_ffn_roofline.py`` divides the two and
can read too high: PERF.md section 7). A program without the counters or
the kernel, as a parent commit may be, gives nothing.
"""

from lib import xplane


def wants(spec: dict) -> list:
    return [spec["pairs"], spec["touched"], spec["calls"]]


def read(spec: dict, run: dict):
    trace = run.get("trace")
    family = run["family"]
    if not trace or not hasattr(family, "grouped_ffn_work"):
        return None
    pairs, touched, calls = (run["edges"].delta(spec[k])
                             for k in ("pairs", "touched", "calls"))
    took = xplane.seconds_matching(trace, spec["pattern"])
    in_slice = xplane.calls_matching(trace, spec["pattern"]) / trace["chips"]
    if not pairs or touched is None or not calls or took <= 0:
        return None
    ops, nbytes = family.grouped_ffn_work(run["cfg"], pairs, touched)
    peaks = run["peaks"]
    need_a_call = max(ops / peaks["flops_bf16"],
                      nbytes / peaks["hbm_bytes_per_s"]) / calls
    return 100.0 * need_a_call * in_slice / took
