"""Peaks of the chips this benchmark may run on: one file for each
``device_kind`` under ``peaks/`` (the kind as JAX reports it, with every
character that a file name may not hold written as ``_``), stating its
published numbers and their source. A device that has no file is an error,
never a default: a share of a peak nobody wrote down is not a number."""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks_for(device_kind: str) -> dict:
    name = re.sub(r"[^A-Za-z0-9_.-]", "_", device_kind)
    path = os.path.join(HERE, "peaks", f"{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; add its "
            f"published numbers, with their source, as benchmarks/peaks/"
            f"{name}.json") from None
