#!/usr/bin/env python3
"""Reads the controls of ``correct`` on the chip, at the cells' own sizes.

    python3 benchmarks/tests/chip_control.py train <cell> <seed> [<seed> ...]
    python3 benchmarks/tests/chip_control.py serve <cell> <seed> <seconds> \
        [<engine arguments to try, as JSON> [<trace>]]

``train``: for each seed the plain reference follows the cell's first steps
three times: in float32 (the reference), with fp8 operands (the control,
one precision below ``mixed_bf16``) and in float32 with half of the
positions left out and the mean taken over the rest (the planted fault).
The latter two are put in the program's place and compared with the first,
number by number, against the configuration's limits. No program runs.

``serve``: one run of the cell with a short window; besides what every run
compares, the same sampled prompts and served tokens go through the
reference with fp8 operands, and the gap of the token it puts first at each
position is read in the float32 reference's logits. The line's ``readings``
hold the largest gap and the share of the gaps beyond ``logit_gap``, of the
run (``gaps_max``, ``gaps_outlier_share``) and of the control
(``control_gaps_max``, ``control_outlier_share``): a configuration's
``logit_gap`` and ``logit_gap_outlier_share`` are set from them. Engine
arguments given here replace the configuration's for that run (how
``prefill_chunk`` 16 and 128 were tried once each, PERF.md). A cell that
BENCHMARK.json lacks is looked for in the tests' own list
(``data/cells.json``, with the families, mixes and readers beside it).

Each line printed is one JSON object; PERF.md holds what was read (PR 24).
Not part of a benchmark run, and not a test that pytest collects.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import run as bench_run  # noqa: E402
from lib import common, control, traffic  # noqa: E402


def cell_files(name: str):
    bench = common.load_json("..", "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(common.ROOT, entry["file"])) as f:
        return cell, json.load(f), traffic.load(cell["traffic"])


def train(cell_name: str, seeds) -> None:
    cell, cfg, mix = cell_files(cell_name)
    common.device_info(cell["chips"], rehearse=False)
    common.use_compile_cache()
    ref, family = common.load_reference(cfg), common.load_family(cfg)
    for seed in seeds:
        for name, kw in (("fp8", {"mode": "fp8"}),
                         ("half_batch", {"fault": control.HALF_BATCH})):
            v = control.control_against_reference(ref, family, cfg, mix,
                                                  seed, cell["chips"], **kw)
            print(json.dumps({"cell": cell_name, "seed": seed,
                              "control": name, "correct": v.correct,
                              "compared": v.compared()}), flush=True)


def serve(cell_name: str, seed: str, seconds: str, engine: str = "{}",
          trace: str = "0") -> None:
    os.environ["BENCH_READINGS"] = "1"
    own = {}
    if cell_name not in [c["name"] for c in common.load_json(
            "..", "BENCHMARK.json")["workloads"]]:
        own = {"benchmark": os.path.join(DATA, "cells.json"), "dirs": [DATA]}
    bench_run.main(["--workload", cell_name, "--seed", seed, "--seconds",
                    seconds, "--trace", trace],
                   env_extra={"control_mode": "fp8",
                              "engine_override": json.loads(engine), **own})


if __name__ == "__main__":
    kind, cell_name, *rest = sys.argv[1:]
    if kind == "train":
        train(cell_name, [int(s) for s in rest])
    else:
        serve(cell_name, *rest)
