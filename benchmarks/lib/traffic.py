"""The one traffic generator: reads a mix's data file and makes its inputs.

A traffic file (``traffic/<name>.json``) holds the schedule itself, so the
work of a cell never depends on ``--seed``; the seed only chooses the token
ids. Two kinds:

``train``              ``batch`` sequences of ``seq_len`` tokens a chip and
                       step; ids and next-token labels from the seed, every
                       step's rows different.
``serve_closed_loop``  ``clients`` callers, each walking its own list of
                       ``[prompt_tokens, output_tokens, document, document
                       _tokens]`` rows and sending the next request when the
                       last is answered. ``document`` is -1 for a prompt
                       that shares nothing; otherwise the prompt is that
                       document's first ``document_tokens`` ids followed by
                       fresh ones (the question), so that requests naming
                       one document share a prefix. A list that runs out
                       starts again, with fresh ids and fresh documents.
"""

from __future__ import annotations

import json
from typing import Iterator, List, Tuple

import numpy as np

from . import common

_DOC = 1 << 20          # keeps document streams apart from request streams


def load(name: str, dirs=(common.BENCH,)) -> dict:
    """``traffic/<name>.json`` of the first of ``dirs`` that has it."""
    with open(common.find(dirs, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, stream)])


def train_batch(mix: dict, vocab: int, seed: int, step: int,
                chips: int = 1) -> Tuple[np.ndarray, np.ndarray]:
    """(ids, labels) of step ``step``: ``[batch * chips, seq_len]`` int32,
    labels the ids shifted by one."""
    rows, t = mix["batch"] * chips, mix["seq_len"]
    ids = _rng(seed, step).integers(0, vocab, (rows, t + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def requests(mix: dict, client: int) -> Iterator[Tuple[int, list]]:
    """(serial, row) for one client, for ever: the list, then again."""
    rows = mix["schedule"][client]
    serial = 0
    while True:
        for row in rows:
            yield serial, row
            serial += 1


def prompt_ids(mix: dict, vocab: int, seed: int, client: int, serial: int,
               row: list) -> np.ndarray:
    """The prompt of one request: document prefix (if any) + fresh ids."""
    n_prompt, _n_out, doc, n_doc = row
    fresh = _rng(seed, client, serial).integers(
        0, vocab, n_prompt - (n_doc if doc >= 0 else 0), dtype=np.int32)
    if doc < 0:
        return fresh
    lap = serial // len(mix["schedule"][client])
    shared = _rng(seed, _DOC + doc, lap).integers(0, vocab, n_doc,
                                                  dtype=np.int32)
    return np.concatenate([shared, fresh])


def totals(mix: dict) -> dict:
    """What one walk through the lists asks for; the file states the same
    under ``totals`` so that a reader can check the mix without running
    it."""
    rows: List[list] = [r for c in mix["schedule"] for r in c]
    return {"requests_per_client": len(mix["schedule"][0]),
            "prompt_tokens_per_client": sum(r[0] for r in rows)
            // len(mix["schedule"]),
            "output_tokens_per_client": sum(r[1] for r in rows)
            // len(mix["schedule"]),
            "longest_request_tokens": max(r[0] + r[1] for r in rows),
            "shared_prompt_token_share": round(
                sum(r[3] for r in rows if r[2] >= 0 and _repeat(mix, r))
                / max(1, sum(r[0] for r in rows)), 4)}


def _repeat(mix: dict, row: list) -> bool:
    """Whether this row's document was named by an earlier row of its
    client (only then can its prefix be in the cache)."""
    for client in mix["schedule"]:
        seen = set()
        for r in client:
            if r is row:
                return r[2] in seen
            seen.add(r[2])
    return False
