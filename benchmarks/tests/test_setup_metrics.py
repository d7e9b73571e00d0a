"""The per-layer metrics that move ``setup_s`` (PR 37): the ``at_open``
reader on a made-up run, the series its metric files name against a
rehearsed start-up's registries, the rehearsed cells' lines, and a program
from before the series (a parent commit), whose line lacks them."""

import json
import os
import subprocess
import sys

import pytest

from lib import common, readers

RUN = os.path.join(common.BENCH, "run.py")
BENCHMARK = common.load_json("..", "BENCHMARK.json")
NEW = {m["name"]: m for m in BENCHMARK["per_layer"]
       if common.load_json("metrics", m["name"] + ".json")["reader"]["kind"]
       == "at_open"}


def metric_file(name):
    return common.load_json("metrics", name + ".json")


def want(metric, stat="value", **labels):
    return {"metric": metric, "labels": labels, "stat": stat}


class FakeEdges:
    """``Edges`` with the first edge's readings given by hand."""

    def __init__(self, start):
        self.start = {common.Edges.key(w): v for w, v in start}


A, B, C, GONE = (want("a_seconds", "sum", phase="x"), want("b_total"),
                 want("c_total", kind="y"), want("gone_total"))


def made_up_run(**more):
    return dict(edges=FakeEdges([(A, 3.0), (B, 4.0), (C, 8.0), (GONE, None)]),
                setup_s=20.0, **more)


@pytest.mark.parametrize("spec, value", [
    ({"counters": [A]}, 3.0),
    ({"counters": [A, B]}, 7.0),
    ({"counters": [A, B], "scale": 100.0}, 700.0),
    ({"counters": [B], "den": [C]}, 0.5),
    ({"counters": [B], "den": [C], "one_minus": True, "scale": 100.0}, 50.0),
    ({"counters": [A, B], "remainder_of": "setup_s"}, 13.0),
    ({"counters": [A], "optional": [GONE, B]}, 7.0),
    ({"counters": [A], "optional": [GONE], "remainder_of": "setup_s"}, 17.0),
    ({"counters": [GONE]}, None),
    ({"counters": [A, GONE]}, None),
    ({"counters": [A], "den": [GONE]}, None),
    ({"counters": [A], "remainder_of": "not_measured"}, None),
])
def test_at_open_on_a_made_up_run(spec, value):
    spec = dict(spec, kind="at_open")
    got = readers.read({"reader": spec}, made_up_run())
    assert got == (None if value is None else pytest.approx(value))
    assert readers.wants(spec) == [w for k in ("counters", "optional", "den")
                                   for w in spec.get(k, [])]
    assert readers.gauges(spec) == []


def test_a_ratio_over_nothing_is_left_out():
    run = dict(edges=FakeEdges([(A, 0.0), (B, 0.0)]))
    spec = {"kind": "at_open", "counters": [A], "den": [B], "one_minus": True}
    assert readers.read({"reader": spec}, run) is None


def test_the_new_metrics_are_the_issues():
    assert set(NEW) == {
        "setup_pre_init_s", "setup_init_s", "setup_warmup_s",
        "setup_compile_wall_s", "setup_trace_lower_s",
        "setup_backend_compile_s", "setup_cache_miss_share",
        "setup_slowest_compile_s", "setup_first_answers_s",
        "setup_unaccounted_s"}
    serve = {c["name"] for c in BENCHMARK["workloads"]} - {"train-opt-t8192"}
    for name, entry in NEW.items():
        f = metric_file(name)
        assert entry["moves"] == f["moves"] == "setup_s"
        assert entry["layer"] == f["layer"] == "start-up"
        assert entry["better"] == "lower"
        cells = set(entry["workloads"])
        assert cells == (serve if name in ("setup_warmup_s",
                                           "setup_first_answers_s")
                         else serve | {"train-opt-t8192"})


@pytest.fixture(scope="module")
def rehearsed_registries():
    """A start-up of both kinds in this process at a tiny size: a graph
    initialised and trained for a step, then served with a decode ladder
    and asked one question."""
    import numpy as np
    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.util import metrics
    net = ComputationGraph(transformer_lm(
        24, n_layers=1, d_model=16, n_heads=2, d_ff=32, seed=5,
        input_ids=True, max_cache_t=32)).init()
    ids = np.arange(16, dtype=np.int32).reshape(2, 8) % 24
    net.fit(iter([(ids, (ids + 1) % 24)]))
    server = InferenceServer(net, decode=dict(
        max_batch=2, page_size=4, pages_per_seq=8, block_len=4,
        prefill_chunk=8))
    try:
        server.decode.submit([1, 2, 3], max_new_tokens=4).wait(120.0)
        yield [server.registry, metrics.REGISTRY]
    finally:
        server.stop(drain=False)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_metric_file_names_series_the_program_keeps(name,
                                                      rehearsed_registries):
    spec = metric_file(name)["reader"]
    for w in readers.wants(spec):
        metric = next((r.get(w["metric"]) for r in rehearsed_registries
                       if r.get(w["metric"]) is not None), None)
        assert metric is not None, w
        labels = w.get("labels") or {}
        series = [s for r in rehearsed_registries if r.get(w["metric"])
                  for s in r.get(w["metric"]).snapshot()["series"]
                  if all(s["labels"].get(k) == v for k, v in labels.items())]
        if w["metric"] != "xla_compile_cache_total":    # no cache, no request
            assert series and all(w["stat"] in s for s in series), w


def test_a_program_without_the_series_gives_a_line_without_them():
    """The parent's registries: the scheduler's ticks are there, nothing
    of start-up is; every reader returns None and none raises."""
    from deeplearning4j_tpu.util.metrics import MetricsRegistry
    old = MetricsRegistry()
    old.histogram("decode_host_tick_seconds", "", ("component",)).observe(
        0.5, component="dispatch")
    old.histogram("xla_compile_seconds", "", ("fn",)).observe(
        2.0, fn="ComputationGraph.train_step")
    wants = [w for n in NEW for w in readers.wants(metric_file(n)["reader"])]
    edges = common.Edges([old], wants)
    edges.open()
    run = {"edges": edges, "setup_s": 30.0, "gauge_peaks": {}}
    line = {n: readers.read(metric_file(n), run) for n in NEW}
    assert {n for n, v in line.items() if v is not None} \
        == {"setup_first_answers_s"}           # reads a series that was there
    assert line["setup_first_answers_s"] == pytest.approx(0.5)


@pytest.mark.parametrize("cell", ["serve-opt-docqa", "train-opt-t8192"])
def test_a_rehearsed_cell_prints_every_new_metric(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_READINGS", None)
    p = subprocess.run([sys.executable, RUN, "--workload", cell, "--seed",
                        "2147483659", "--seconds", "1", "--trace", "1",
                        "--rehearse"], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    mine = {n for n, m in NEW.items() if cell in m["workloads"]}
    assert mine <= set(metrics), mine - set(metrics)
    assert not (set(NEW) - mine) & set(metrics)
    for n in mine:
        assert metrics[n]["unit"] == NEW[n]["unit"]
        assert metrics[n]["value"] >= 0.0, n
    assert metrics["setup_cache_miss_share"]["value"] <= 100.0
    assert metrics["setup_compile_wall_s"]["value"] > 0.0
