#!/usr/bin/env python3
"""Draws the tests' own serving schedule once and writes it into
``chat-long.json``, as ``benchmarks/traffic/make_traffic.py`` draws the
cells' (``python3 benchmarks/tests/data/traffic/make_traffic.py`` rewrites
the file to the same bytes). No test and no run calls this."""

import importlib.util
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
spec = importlib.util.spec_from_file_location(
    "make_traffic", os.path.join(BENCH, "traffic", "make_traffic.py"))
cells = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cells)


def chat_long(constant: int, clients: int, per_client: int) -> list:
    rng = np.random.default_rng(constant)
    return [[[int(round(math.exp(rng.uniform(math.log(32), math.log(256))))),
              int(rng.integers(96, 129)), -1, 0] for _ in range(per_client)]
            for _ in range(clients)]


if __name__ == "__main__":
    cells.HERE = HERE
    cells.write("chat-long", {
        "kind": "serve_closed_loop",
        "why": "answers of 96 tokens or more, so that the 16 requests the "
               "comparison may draw hold at least 1,536 served tokens: a "
               "configuration that states a share of outliers is held to "
               "check_tokens of 1,000 or more (rehearsed: 16 x 48 = 768)",
        "who": "nobody: the chat mix with longer answers, for the tests' "
               "routed configuration",
        "clients": 8, "stagger_s": 0.4,
        "rehearsal": {"schedule_scale": 2, "stagger_s": 0.0},
        "distribution": "prompt tokens log-uniform on [32, 256], output "
                        "tokens uniform on [96, 128], no shared prefix; "
                        "greedy, no end-of-sequence id",
        "generator_constant": 290001, "generator":
            "numpy default_rng(constant), one pass, client by client",
        "schedule": chat_long(290001, 8, 24)})
