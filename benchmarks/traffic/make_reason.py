#!/usr/bin/env python3
"""Draws the ``reason`` serving schedule once and writes ``reason.json``.

Kept beside the file, as ``make_traffic.py`` is beside ``chat.json`` and
``docqa.json``, so that a reader can see how the lists were drawn and draw
them again (``python3 benchmarks/traffic/make_reason.py`` rewrites
``reason.json`` to the same bytes). The benchmark itself never runs this:
it reads the lists.
"""

import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
from make_traffic import write  # noqa: E402

CONSTANT = 330001


def reason(constant: int, clients: int, per_client: int) -> list:
    """A question of 64 to 1,024 tokens (log-uniform) and a chain of
    thought of 256 to 768 (uniform) for each request."""
    rng = np.random.default_rng(constant)
    out = []
    for _ in range(clients):
        rows = []
        for _ in range(per_client):
            prompt = int(round(math.exp(rng.uniform(math.log(64),
                                                    math.log(1024)))))
            rows.append([prompt, int(rng.integers(256, 769)), -1, 0])
        out.append(rows)
    return out


if __name__ == "__main__":
    write("reason", {
        "kind": "serve_closed_loop",
        "why": "decode takes nearly all the time (58 % of the computed "
               "tokens, one a lane a step, where a prefill chunk computes "
               "128 a lane a dispatch) at a batch of 32 lanes, where a step "
               "is bound by memory traffic of three kinds side by side: the "
               "held experts' matrices, the other weights, and the lanes' "
               "recurrent state read and written; routing is uneven from "
               "step to step; no shared prefix",
        "who": "users of a reasoning assistant: a question of a few hundred "
               "tokens in, a chain of thought of several hundred out",
        "clients": 32, "stagger_s": 0.25,
        "rehearsal": {"schedule_scale": 32, "stagger_s": 0.0},
        "distribution": "32 clients, one a lane, each a list of 12 requests "
                        "walked again when it ends; prompt tokens log-uniform "
                        "on [64, 1024], output tokens uniform on [256, 768], "
                        "no shared prefix; greedy, no end-of-sequence id, so "
                        "every request runs to its stated length; the longest "
                        "request is 1,792 tokens, inside a window of 2,048; "
                        "the clients' lengths differ, so their phases sweep",
        "generator_constant": CONSTANT, "generator":
            "numpy default_rng(constant), one pass, client by client",
        "schedule": reason(CONSTANT, 32, 12)})
