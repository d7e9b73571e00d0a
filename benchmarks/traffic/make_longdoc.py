#!/usr/bin/env python3
"""Draws the ``longdoc`` serving schedule once and writes ``longdoc.json``.

Kept beside the file, as ``make_reason.py`` is beside ``reason.json``, so
that a reader can see how the lists were drawn and draw them again
(``python3 benchmarks/traffic/make_longdoc.py`` rewrites ``longdoc.json`` to
the same bytes). The benchmark itself never runs this: it reads the lists.
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
from make_traffic import write  # noqa: E402

CONSTANT = 350001
TURNS, DOCUMENTS = 16, 4


def longdoc(constant: int, clients: int) -> list:
    """Each client walks ``DOCUMENTS`` documents of its own, 2,048 to 6,144
    tokens (uniform), and puts ``TURNS`` turns to each in a row: a question
    of 16 to 64 fresh tokens after the document, an answer of 128 to 384
    (both uniform). Client ``c``'s list begins at turn ``c mod TURNS`` of
    its first document, so the clients' misses fall all over a window and
    not at its start."""
    rng = np.random.default_rng(constant)
    out = []
    for c in range(clients):
        rows = []
        for d in range(DOCUMENTS):
            doc_tokens = int(rng.integers(2048, 6145))
            for turn in range(TURNS):
                question = int(rng.integers(16, 65))
                answer = int(rng.integers(128, 385))
                if d == 0 and turn < c % TURNS:
                    continue        # drawn all the same: one stream a client
                rows.append([doc_tokens + question, answer,
                             c * DOCUMENTS + d, doc_tokens])
        out.append(rows)
    return out


if __name__ == "__main__":
    write("longdoc", {
        "kind": "serve_closed_loop",
        "why": "decode at 32 lanes over contexts of 2k to 6.6k tokens takes "
               "most of the time: a step reads the touched experts' "
               "matrices, the attention's and the dense weights, and every "
               "live token's latent row (a read at 128 query heads a row), "
               "three kinds of work side by side; 15 of 16 prompts find "
               "their document in the prefix cache, which holds 32 long "
               "documents under an expert layer; a miss is prefilled in "
               "chunks of 128 between decode blocks",
        "who": "users of an assistant working over one long document or "
               "tool corpus: an agent loop that puts turn after turn to the "
               "same long shared context (workloads.md, the MLA pairing; "
               "its 16k+ contexts cut to what a 50 s window finishes tens "
               "of)",
        "clients": 32, "stagger_s": 0.25,
        "rehearsal": {"schedule_scale": 64, "stagger_s": 0.0},
        "distribution": "32 clients, one a lane; a client takes a document "
                        "of its own and puts 16 turns to it in a row, then "
                        "the next document; 4 documents a list, walked "
                        "again with fresh ids when it ends; client c's list "
                        "begins at turn c mod 16 of its first document; "
                        "document tokens uniform on [2048, 6144], a turn's "
                        "question uniform on [16, 64] fresh tokens after the "
                        "document, its answer uniform on [128, 384]; greedy, "
                        "no end-of-sequence id, so every request runs to its "
                        "stated length; the longest request is at most "
                        "6,592 tokens, inside a window of 8,192",
        "generator_constant": CONSTANT, "generator":
            "numpy default_rng(constant), one pass, client by client",
        "schedule": longdoc(CONSTANT, 32)})
