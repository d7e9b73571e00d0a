#!/usr/bin/env python3
"""Draws the ``longprompt`` serving schedule once and writes
``longprompt.json``.

Kept beside the file, as ``make_reason.py`` is beside ``reason.json``, so
that a reader can see how the lists were drawn and draw them again
(``python3 benchmarks/traffic/make_longprompt.py`` rewrites
``longprompt.json`` to the same bytes). The benchmark itself never runs
this: it reads the lists.
"""

import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
from make_traffic import write  # noqa: E402

CONSTANT = 410001


def longprompt(constant: int, clients: int, per_client: int) -> list:
    """A fresh prompt of 2,048 to 8,192 tokens (log-uniform) and an answer
    of 128 to 512 (uniform) for each request; nothing shared."""
    rng = np.random.default_rng(constant)
    out = []
    for _ in range(clients):
        rows = []
        for _ in range(per_client):
            prompt = int(round(math.exp(rng.uniform(math.log(2048),
                                                    math.log(8192)))))
            rows.append([prompt, int(rng.integers(128, 513)), -1, 0])
        out.append(rows)
    return out


if __name__ == "__main__":
    write("longprompt", {
        "kind": "serve_closed_loop",
        "why": "prefill-heavy serving of a hybrid at 32 lanes: long fresh "
               "prompts go through three KDA layers' chunked delta rule "
               "and one GQA layer's paged read for each, in chunks of 128 "
               "between decode blocks; a decode step reads and writes "
               "every lane's fixed-size KDA state",
        "who": "people who send a long fresh document, retrieved context "
               "or source file to a hybrid long-context model and get a "
               "short answer: RAG and summarisation front ends",
        "clients": 32, "stagger_s": 0.25,
        "rehearsal": {"schedule_scale": 64, "stagger_s": 0.0},
        "distribution": "32 clients, one a lane, each a list of 12 requests "
                        "walked again when it ends; prompt tokens "
                        "log-uniform on [2048, 8192] (mean about 4,430), "
                        "answer tokens uniform on [128, 512], no shared "
                        "prefix; ids uniform over the configuration's "
                        "vocabulary; greedy, no end-of-sequence id, so every "
                        "request runs to its stated length; the longest "
                        "request is at most 8,704 tokens, within the window",
        "generator_constant": CONSTANT, "generator":
            "numpy default_rng(constant), one pass, client by client",
        "schedule": longprompt(CONSTANT, 32, 12)})
