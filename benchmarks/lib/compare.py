"""The comparison that decides ``correct``: numbers, each beside its limit.

A cell's runner collects what the timed path produced and what the plain
reference makes of the same inputs, and hands both here. Each number that
is compared has a limit of its own, kept in the configuration's file under
``limits`` with the readings it was set from in PERF.md. ``Verdict`` holds
the pairs and prints them: the last lines on standard error, and the
``compared`` key of the result line.
"""

from __future__ import annotations

import statistics
import sys
from typing import Dict, List, Optional


class Verdict:
    def __init__(self):
        self.rows: List[dict] = []

    def add(self, name: str, value: Optional[float], limit: float,
            note: str = "") -> None:
        """``value`` above ``limit``, missing or not finite fails."""
        ok = (value is not None and value == value
              and abs(value) != float("inf") and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok), **({"note": note} if note else {})})

    def require(self, name: str, ok: bool, note: str = "") -> None:
        """An exact requirement (limit 0 on the count of violations)."""
        self.add(name, 0.0 if ok else 1.0, 0.0, note)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def compared(self) -> dict:
        return {r["name"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}

    def print(self) -> None:
        for r in self.rows:
            print(f"compared {r['name']}: {r['value']!r} (limit {r['limit']!r})"
                  f" {'ok' if r['ok'] else 'FAILED'}"
                  + (f"  [{r['note']}]" if r.get("note") else ""),
                  file=sys.stderr)
        print(f"correct: {self.correct}", file=sys.stderr, flush=True)


# -- training ---------------------------------------------------------------

def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   skip=()) -> dict:
    """The largest gap, over the leaves, between the program's norm and the
    reference's (not the norm of a difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns the gap and the leaf it is at."""
    names = [k for k in reference if k not in skip]
    median = statistics.median(reference[k] for k in names)
    worst, at = 0.0, None
    for k in names:
        if k not in program:
            return {"gap": float("inf"), "leaf": k, "median": median}
        gap = abs(program[k] - reference[k]) / max(reference[k], median)
        if at is None or gap > worst:
            worst, at = gap, k
    return {"gap": worst, "leaf": at, "median": median}


def rounding_only_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose gradient is nought to rounding in the reference: under a
    thousandth of the median leaf's. Under Adam they move by round-off
    alone, so they are left out of the change (by this rule, not by name)."""
    median = statistics.median(ref_grad_norms.values())
    return sorted(k for k, v in ref_grad_norms.items() if v < 1e-3 * median)


def worst_leaf_diff(diff_norms: Dict[str, float],
                    reference: Dict[str, float]) -> dict:
    """The largest, over the leaves, of the norm of (program's gradient
    minus reference's) against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = statistics.median(reference.values())
    worst, at = 0.0, None
    for k, ref_norm in reference.items():
        if k not in diff_norms:
            return {"gap": float("inf"), "leaf": k}
        gap = diff_norms[k] / max(ref_norm, median)
        if at is None or gap > worst:
            worst, at = gap, k
    return {"gap": worst, "leaf": at}


def compare_training(verdict: Verdict, limits: dict, program: dict,
                     reference: dict) -> None:
    """``program`` / ``reference``: ``{"losses": [...], "grad_norms": {leaf:
    norm}, "change_norms": {leaf: norm}}`` over the same first steps;
    ``reference["grad_diff_norms"]`` holds, leaf by leaf, the norm of the
    difference of the two sides' first gradients. The losses are not
    compared (PERF.md section 2: no fault and no control moves them)."""
    n = len(reference["losses"])
    verdict.require("steps_compared", len(program["losses"]) >= n,
                    f"{len(program['losses'])} of {n}")
    g = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    verdict.add("grad_norm_worst_leaf_gap", g["gap"],
                limits["grad_norm_gap"], str(g["leaf"]))
    d = worst_leaf_diff(reference["grad_diff_norms"],
                        reference["grad_norms"])
    verdict.add("grad_diff_worst_leaf", d["gap"], limits["grad_diff"],
                str(d["leaf"]))
    skip = rounding_only_leaves(reference["grad_norms"])
    c = worst_leaf_gap(program["change_norms"], reference["change_norms"],
                       skip)
    verdict.add("change_norm_worst_leaf_gap", c["gap"],
                limits["change_norm_gap"],
                f"{c['leaf']}; left out: {len(skip)}")


# -- serving ----------------------------------------------------------------

def token_gaps(ref_logits, tokens) -> list:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where it IS the best)."""
    import numpy as np
    z = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens, np.int64)
    return (z.max(axis=-1) - z[np.arange(len(tok)), tok]).tolist()


def outlier_share(gaps, limit: float):
    """The share of the gaps that lie beyond ``limit`` (one that is not a
    number lies beyond any); None of no gaps."""
    return sum(not g <= limit for g in gaps) / len(gaps) if gaps else None
