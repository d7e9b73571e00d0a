#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``configs/<config>.json``, with its plain
reference beside it), a traffic mix (``traffic/<traffic>.json``) and, through
BENCHMARK.json's ``per_layer`` entries, its per-layer metrics
(``metrics/<metric>.json``, read by a kind of ``lib/readers.py`` or by
``readers/<kind>.py``). The configuration's ``model_type`` names its family
(``families/<model_type>.py``): the program's builder, the leaves of the
weights, the operation counts. Nothing here names a cell, a configuration, a
mix, a metric, a family or a size of a model: a later PR adds files and
entries, and edits none.

``--seed`` makes the weights and the token ids and nothing else; the work is
in the traffic file. With ``--trace 0`` the last line of standard output
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, the device's busy time and a breakdown of the traced slice.
Without a TPU (or with fewer chips than the cell asks for) it exits non-zero
and prints no result. ``--rehearse`` runs the cell at the tiny sizes the
configuration and the mix state under ``rehearsal``, on whatever backend JAX
has, with the Pallas kernels interpreted: it debugs the harness on a CPU and
is never a fallback.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from lib import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def rehearsal_sizes(cfg: dict, mix: dict):
    """The tiny stand-ins a configuration and a mix state for rehearsals;
    a serving schedule's token counts are divided by ``schedule_scale``."""
    cfg = {**cfg, **cfg["rehearsal"]}
    mix = {**mix, **mix.get("rehearsal", {})}
    k = mix.get("schedule_scale")
    if k and "schedule" in mix:
        def small(row):
            prompt, out, doc, doc_tokens = row
            if doc < 0:
                return [max(2, prompt // k), max(2, out // k), doc, 0]
            shared = max(2, doc_tokens // k)
            return [shared + max(1, (prompt - doc_tokens) // k),
                    max(2, out // k), doc, shared]
        mix["schedule"] = [[small(r) for r in c] for c in mix["schedule"]]
    return cfg, mix


def metric_value(v: float) -> float:
    """A tail that a failed request pushed beyond any value is printed as a
    number no run reaches, since JSON has no infinity."""
    return v if math.isfinite(v) else 1e12


def main(argv=None, env_extra=None) -> int:
    args = parse(argv)
    env_extra = dict(env_extra or {})
    # tests bring a cell list and directories of their own, laid out like
    # benchmarks/ and looked into before it, for families, mixes and readers
    dirs = tuple(env_extra.pop("dirs", ())) + (common.BENCH,)
    with open(env_extra.pop("benchmark", os.path.join(
            ROOT, "BENCHMARK.json"))) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no cell {args.workload!r} in the cell list "
              f"(cells: {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf_entry["file"])) as f:
        cfg = json.load(f)
    family = common.load_family(cfg, dirs)
    from lib import traffic
    mix = traffic.load(cell["traffic"], dirs)
    e2e_names = [m["name"] for m in bench["end_to_end"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
    layer_files = []
    for m in bench["per_layer"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            with open(common.find(dirs, "metrics", f"{m['name']}.json")) as f:
                layer_files.append(json.load(f))

    device = common.device_info(cell["chips"], args.rehearse)
    common.use_compile_cache()
    from lib import peaks, readers, xplane
    from deeplearning4j_tpu.util.xla import interpret_kernels
    mode = contextlib.nullcontext()
    if args.rehearse:
        cfg, mix = rehearsal_sizes(cfg, mix)
        cfg.update(env_extra.get("rehearsal_sizes", {}))
        os.environ["DL4JTPU_FLASH_ATTENTION"] = "1"
        if device["platform"] != "tpu":
            mode = interpret_kernels()
        chip_peaks = peaks.peaks_for("TPU v5 lite")   # shares mean nothing here
    else:
        chip_peaks = peaks.peaks_for(device["kind"])

    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    env = {"wants": [w for m in layer_files
                     for w in readers.wants(m["reader"], dirs)],
           "gauges": [g for m in layer_files
                      for g in readers.gauges(m["reader"], dirs)],
           "family": family,
           "compiles": common.CompileCounter(), "trace_dir": trace_dir,
           # only the process that holds a chip can trace it: a rehearsal
           # off the TPU still reports the per-layer metrics that need none
           "tracing": bool(args.trace) and device["platform"] == "tpu"}
    env.update(env_extra)
    runner = {"train": "train_cell", "serve": "serve_cell"}[cfg["runner"]]
    module = __import__(f"lib.{runner}", fromlist=["run"])
    with mode:
        run = module.run(cell, cfg, mix, args, env)

    run.update(cfg=cfg, mix=mix, peaks=chip_peaks, family=family)
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    result = {"correct": run["verdict"].correct,
              "attempted": run["attempted"], "failed": run["failed"]}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if args.trace:
        if env["tracing"]:
            trace = xplane.load(trace_dir)
            reduced = xplane.reduce(
                trace, xplane.find_span(trace, common.WINDOW_SPAN),
                ignore=(common.WINDOW_SPAN,))
            shutil.rmtree(trace_dir, ignore_errors=True)
            run["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = xplane.breakdown(reduced)
        metrics = {}
        for m in layer_files:
            v = readers.read(m, run, dirs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        result["metrics"] = metrics
    else:
        result["metrics"] = {
            n: {"value": metric_value(run["end_to_end"][n]),
                "unit": units[n]}
            for n in e2e_names if n in run["end_to_end"]}
    result["device"] = device
    result["window_s"] = run["window_s"]
    result["reference_s"] = run.get("reference_s")
    if os.environ.get("BENCH_READINGS"):     # for setting limits, by hand
        result["readings"] = dict(run.get("readings", {}),
                                  memory_stats=common.LAST_MEMORY_STATS)
    result["compared"] = run["verdict"].compared()
    sys.stdout.flush()
    run["verdict"].print()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
