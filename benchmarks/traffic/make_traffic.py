#!/usr/bin/env python3
"""Draws the serving schedules once and writes them into the traffic files.

Kept beside the files so that a reader can see how the lists were drawn and
draw them again (``python3 benchmarks/traffic/make_traffic.py`` rewrites
``chat.json`` and ``docqa.json`` to the same bytes). The benchmark itself
never runs this: it reads the lists.
"""

import json
import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from lib import traffic  # noqa: E402


def chat(constant: int, clients: int, per_client: int) -> list:
    rng = np.random.default_rng(constant)
    out = []
    for _ in range(clients):
        rows = []
        for _ in range(per_client):
            prompt = int(round(math.exp(rng.uniform(math.log(32),
                                                    math.log(512)))))
            rows.append([prompt, int(rng.integers(32, 129)), -1, 0])
        out.append(rows)
    return out


def docqa(constant: int, clients: int, docs: int, questions: int) -> list:
    """Client ``c`` of ``clients`` draws its documents from the c-th equal
    part of [1024, 1536] and its answers from the c-th part of [16, 32]:
    the clients' rounds (a document and its questions) then differ in
    length by about a quarter, so their phase against each other runs
    through every value several times in a window. With one range for all,
    the phase stayed for a whole window near where set-up had left it, and
    the rate followed it (PERF.md section 6, PR 29)."""
    rng = np.random.default_rng(constant)
    out = []
    for c in range(clients):
        doc_lo, doc_hi = (1024 + 512 * c // clients,
                          1024 + 512 * (c + 1) // clients)
        ans_lo, ans_hi = 16 + 16 * c // clients, 16 + 16 * (c + 1) // clients
        rows = []
        for d in range(docs):
            n_doc = int(rng.integers(doc_lo, doc_hi + 1))
            for _ in range(questions):
                rows.append([n_doc + int(rng.integers(16, 33)),
                             int(rng.integers(ans_lo, ans_hi + 1)),
                             c * docs + d, n_doc])
        out.append(rows)
    return out


def write(name: str, mix: dict) -> None:
    mix["totals"] = traffic.totals(mix)
    head = {k: v for k, v in mix.items() if k != "schedule"}
    text = json.dumps(head, indent=1)[:-2] + ',\n "schedule": [\n' + ",\n".join(
        "  " + json.dumps(c, separators=(",", ":")) for c in mix["schedule"]
    ) + "\n ]\n}\n"
    with open(os.path.join(HERE, f"{name}.json"), "w") as f:
        f.write(text)


if __name__ == "__main__":
    write("chat", {
        "kind": "serve_closed_loop",
        "why": "decode-bound: the fused decode block, the scheduler and the "
               "paged read do nearly all the work; prefill little, the "
               "prefix cache nothing (no two prompts share a token run)",
        "who": "chat users: a short to medium prompt, a paragraph back",
        "clients": 8, "stagger_s": 0.4,
        "rehearsal": {"schedule_scale": 16, "stagger_s": 0.0},
        "distribution": "prompt tokens log-uniform on [32, 512], output "
                        "tokens uniform on [32, 128], no shared prefix; "
                        "greedy, no end-of-sequence id, so every request "
                        "runs to its stated length",
        "generator_constant": 240001, "generator":
            "numpy default_rng(constant), one pass, client by client",
        "schedule": chat(240001, 8, 48)})
    write("docqa", {
        "kind": "serve_closed_loop",
        "why": "prefill-bound: chunked prefill of a 1.0-1.5k-token "
               "document, then three more questions of it answered from "
               "the prefix cache; decode does little (16-32 tokens)",
        "who": "document question answering and retrieval front ends",
        "clients": 2, "stagger_s": 0.4,
        "rehearsal": {"schedule_scale": 16, "stagger_s": 0.0},
        "why_two_clients": "a request holds up to 100 of the arena's 224 "
                           "pages (see the configuration's assumed.arena), "
                           "so two run at once; eight clients return with "
                           "an arena that holds them (PERF.md, Open "
                           "questions)",
        "distribution": "each client takes a document and asks 4 questions "
                        "of it in a row (question uniform [16, 32] tokens "
                        "after the document), then the next document; the "
                        "first client's documents are uniform [1024, 1280] "
                        "tokens and its answers uniform [16, 24], the "
                        "second's [1280, 1536] and [24, 32], so that their "
                        "rounds differ in length and their phase against "
                        "each other sweeps through every window; documents "
                        "are not shared between clients; greedy, no "
                        "end-of-sequence id",
        "generator_constant": 240002, "generator":
            "numpy default_rng(constant), one pass, client by client",
        "schedule": docqa(240002, 2, 24, 4)})
