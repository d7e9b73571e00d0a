"""The controls of ``correct``: the plain reference put in the program's
place, computed one precision below what the configuration states (fp8
operands for ``mixed_bf16``), or with a fault planted in it. None of this
runs in a benchmark run: ``tests/chip_control.py`` reads the controls on the
chip at the cells' own sizes (PERF.md has the readings the limits were set
from), and ``tests/test_control.py`` keeps them failing at a small size.
"""

from __future__ import annotations

import numpy as np

from . import compare, traffic, weights

HALF_BATCH = "half_batch"     # half of the rows' positions left out, the
                              # mean taken over the rest


def half_batch(rows):
    """The later half of the rows left out and the mean taken over the
    rest: the same as the first half sent twice."""
    h = len(rows) // 2
    return rows if h == 0 else np.concatenate([rows[:h], rows[:h]])


def reference_training(ref, family, cfg: dict, mix: dict, seed: int,
                       chips: int = 1,
                       mode: str = "f32", fault: str = None,
                       first_grads=None, keep_first_grads=False) -> dict:
    """The first ``check_steps`` steps by the plain reference, as the
    dict ``compare.compare_training`` takes on either side. With
    ``first_grads`` (the other side's first gradients, on the host) the
    norm of each leaf's difference from them comes back too."""
    steps = int(mix.get("check_steps", 3))
    t = mix["seq_len"]
    batches = [traffic.train_batch(mix, cfg["vocab_size"], seed, s, chips)
               for s in range(steps)]
    token_weight = None
    if fault == HALF_BATCH:
        batches = [tuple(half_batch(a) for a in b) for b in batches]
        if len(batches[0][0]) == 1:       # one row: its later positions
            token_weight = np.where(np.arange(t) < t // 2, 2.0, 0.0)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    return ref.train_steps(
        weights.make_weights(family, cfg, seed), batches, cfg=cfg, mode=mode,
        token_weight=token_weight, first_grads=first_grads,
        keep_first_grads=keep_first_grads)


def control_against_reference(ref, family, cfg, mix, seed, chips=1,
                              **control):
    """A control (``mode="fp8"``) or a planted fault (``fault=...``) put in
    the program's place: it runs first and keeps its first gradients, then
    the float32 reference follows the same steps and both go to the
    comparison. Returns the verdict."""
    put = reference_training(ref, family, cfg, mix, seed, chips,
                             keep_first_grads=True, **control)
    sound = reference_training(ref, family, cfg, mix, seed, chips,
                               first_grads=put.pop("first_grads"))
    return training_verdict(cfg, put, sound)


def training_verdict(cfg: dict, program: dict, reference: dict):
    verdict = compare.Verdict()
    compare.compare_training(verdict, cfg["limits"], program, reference)
    return verdict


def lower_precision_gaps(ref_logits, low_logits) -> list:
    """Serving's control need not decode: at each position of the same
    prompts and served tokens, the gap, in the reference's logits, of the
    token that the lower precision puts first."""
    return [g for z, zl in zip(ref_logits, low_logits)
            for g in compare.token_gaps(z, np.argmax(np.asarray(zl), -1))]
