"""The rotary position term shared by the attention layers that turn their
queries and keys by the ABSOLUTE position (``nn/conf/mla``'s rotary columns,
``nn/conf/eva``'s whole heads)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def rotary(x, positions, theta: float):
    """The rotary map over the last axis of ``x`` (``r`` wide, even): pair
    ``i`` is columns ``(i, i + r/2)`` (the ``rotate_half`` pairing), turned
    by the angle ``t / theta^(2i/r)``, ``t`` the ABSOLUTE position.
    ``positions`` has ``x``'s leading axes up to the position axis (``[b,
    t]`` for ``x [b, t, r]`` or ``[b, t, h, r]``). Angles, sines and the
    product in float32; returns float32."""
    r = x.shape[-1]
    inv_freq = jnp.asarray(
        1.0 / np.power(float(theta), np.arange(0, r, 2) / float(r)),
        jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    ang = ang.reshape(ang.shape[:-1] + (1,) * (x.ndim - ang.ndim)
                      + ang.shape[-1:])
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
