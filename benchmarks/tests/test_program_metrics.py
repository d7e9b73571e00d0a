"""The per-layer metrics that read what the program measures of itself
(PR 26: the ``region`` seam's histograms and the bytes counter): each is a
file of an existing reader kind, and a traced rehearsal of each of its
cells prints it with a finite value."""

import json
import math

import pytest

from lib import common, readers
from test_rehearsal import run_cell

NEW = ("engine_fetch_share", "engine_device_wait_share",
       "engine_enqueue_share", "prefill_fetch_share",
       "d2h_kib_per_decoded_token", "sched_idle_wait_share",
       "ttft_queue_wait_share", "queue_wait_on_pages_share",
       "delivery_gap_mean_ms_obs", "fit_device_wait_share",
       "fit_dispatch_share")
BENCH = common.load_json("..", "BENCHMARK.json")
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
CELLS = sorted({c for n in NEW for c in ENTRIES[n]["workloads"]})


@pytest.mark.parametrize("name", NEW)
def test_file_is_data_for_a_reader_that_exists(name):
    spec = common.load_json("metrics", f"{name}.json")
    assert spec["reader"]["kind"] in readers.KINDS
    entry = ENTRIES[name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert readers.wants(spec["reader"]), "it reads the program's registry"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_prints_each_new_metric(cell):
    p = run_cell("--workload", cell, "--seed", "2147483659", "--seconds",
                 "1", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    mine = [n for n in NEW if cell in ENTRIES[n]["workloads"]]
    assert mine
    for name in mine:
        assert name in line["metrics"], name
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0.0, (name, value)
        assert line["metrics"][name]["unit"] == ENTRIES[name]["unit"]
    if "engine_fetch_share" in mine:
        # the three phases are the dispatch: together they are most of a
        # saturated window, and no single one exceeds it
        m = line["metrics"]
        total = sum(m[n]["value"] for n in ("engine_enqueue_share",
                                            "engine_device_wait_share",
                                            "engine_fetch_share"))
        assert 0.0 < total <= 100.0 + 1e-6
        assert m["prefill_fetch_share"]["value"] <= \
            m["engine_fetch_share"]["value"] + 1e-9
        assert m["d2h_kib_per_decoded_token"]["value"] > 0.0
