#!/usr/bin/env python3
"""Draws the ``longbytes`` serving schedule once and writes
``longbytes.json``.

Kept beside the file, as ``make_reason.py`` is beside ``reason.json``, so
that a reader can see how the lists were drawn and draw them again
(``python3 benchmarks/traffic/make_longbytes.py`` rewrites
``longbytes.json`` to the same bytes). The benchmark itself never runs
this: it reads the lists.
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
from make_traffic import write  # noqa: E402

CONSTANT = 390001


def longbytes(constant: int, clients: int, per_client: int) -> list:
    """A fresh byte prompt of 8,192 to 28,672 bytes and an answer of 256 to
    1,024 bytes (both uniform) for each request; nothing shared."""
    rng = np.random.default_rng(constant)
    return [[[int(rng.integers(8192, 28673)), int(rng.integers(256, 1025)),
              -1, 0] for _ in range(per_client)] for _ in range(clients)]


if __name__ == "__main__":
    write("longbytes", {
        "kind": "serve_closed_loop",
        "why": "decode at 8 lanes over contexts of 8k to 30k bytes: a step "
               "reads the weights once and, in every layer, each lane's "
               "current window of up to 2,048 raw K/V rows and one summary "
               "row for every 16 bytes before it, the EVA read some 58 to "
               "69 % of the step's bytes; a window's pages go back at every "
               "2,048th byte; long prompts are prefilled in chunks of 128 "
               "between decode blocks",
        "who": "users of a tokenizer-free byte-level model who continue "
               "long source files or documents from their raw bytes",
        "clients": 8, "stagger_s": 0.25,
        "rehearsal": {"schedule_scale": 64, "stagger_s": 0.0},
        "distribution": "8 clients, one a lane, each a list of 10 requests "
                        "walked again when it ends; prompt bytes uniform on "
                        "[8192, 28672], answer bytes uniform on [256, 1024], "
                        "no shared prefix; ids uniform over the "
                        "configuration's vocabulary; greedy, no "
                        "end-of-sequence id, so every request runs to its "
                        "stated length; the longest request is at most "
                        "29,696 bytes, inside the published 32,768",
        "generator_constant": CONSTANT, "generator":
            "numpy default_rng(constant), one pass, client by client",
        "schedule": longbytes(CONSTANT, 8, 10)})
