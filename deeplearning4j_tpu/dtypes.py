"""Dtype policy for TPU execution.

The reference (ND4J) has a single global data-type (float/double) set on the
Nd4j factory. On TPU the idiomatic split is: parameters and optimizer state in
float32, matmul/conv compute in bfloat16 (MXU native), reductions/softmax in
float32. This module provides a policy object threaded through layer apply
functions, plus a global default.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """What dtype to use where.

    param_dtype:   dtype parameters are stored in (float32 for stable updates).
    compute_dtype: dtype inputs/params are cast to for matmul/conv (bfloat16
                   keeps the MXU fed at full rate on TPU).
    output_dtype:  dtype activations are returned in (None = compute_dtype).
    """

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    output_dtype: Any = None

    def cast_to_compute(self, *arrays):
        out = tuple(
            a.astype(self.compute_dtype) if hasattr(a, "astype") else a for a in arrays
        )
        return out[0] if len(out) == 1 else out

    def cast_output(self, array):
        dt = self.output_dtype or self.compute_dtype
        return array.astype(dt)


FLOAT32 = DtypePolicy()
# Mixed precision: bf16 compute, f32 params — the TPU training default.
MIXED_BF16 = DtypePolicy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                         output_dtype=jnp.bfloat16)
FLOAT64 = DtypePolicy(param_dtype=jnp.float64, compute_dtype=jnp.float64)
# Stored bf16: parameters kept in bfloat16 as a served checkpoint is, bf16
# compute. Serving only (a bf16 master copy does not train). What must stay
# float32 does so where it is computed: router scores, softmax and norm
# statistics, a state-space layer's dt, A and carried state, the K/V pools.
STORED_BF16 = DtypePolicy(param_dtype=jnp.bfloat16,
                          compute_dtype=jnp.bfloat16,
                          output_dtype=jnp.bfloat16)

_default_policy = FLOAT32


def default_policy() -> DtypePolicy:
    return _default_policy


def set_default_policy(policy: DtypePolicy) -> None:
    global _default_policy
    _default_policy = policy


def policy_from_name(name: str) -> DtypePolicy:
    name = name.lower()
    if name in ("float32", "f32", "single"):
        return FLOAT32
    if name in ("bfloat16", "bf16", "mixed", "mixed_bf16", "mixed_bfloat16"):
        return MIXED_BF16
    if name in ("stored_bf16", "bf16_stored", "bfloat16_stored"):
        return STORED_BF16
    if name in ("float64", "f64", "double"):
        return FLOAT64
    raise ValueError(f"unknown dtype policy {name!r}")
