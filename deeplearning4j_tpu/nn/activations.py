"""Activation functions.

String-keyed registry matching the reference's activation-function strings
(reference ``nn/conf/NeuralNetConfiguration.java:480`` — default "sigmoid";
ND4J op factory names: sigmoid, tanh, relu, leakyrelu, softmax, identity,
softplus, softsign, hardtanh, hardsigmoid, elu, cube, rationaltanh).

All are pure jnp functions; derivatives come from JAX autodiff (the reference
hand-codes derivative ops — ``nn/layers/BaseLayer.java:147``).
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

Activation = Callable[[jax.Array], jax.Array]

_REGISTRY: Dict[str, Activation] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name.lower()] = fn
        return fn
    return deco


def get(name: str) -> Activation:
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown activation {name!r}; known: {sorted(_REGISTRY)}") from None


def names():
    return sorted(_REGISTRY)


@register("identity")
@register("linear")
def identity(x):
    return x


@register("sigmoid")
def sigmoid(x):
    return jax.nn.sigmoid(x)


@register("tanh")
def tanh(x):
    return jnp.tanh(x)


@register("relu")
def relu(x):
    return jax.nn.relu(x)


@register("relu2")
def relu2(x):
    """Squared ReLU (Primer; the non-gated experts of ``nemotron_h``)."""
    return jnp.square(jax.nn.relu(x))


@register("leakyrelu")
def leakyrelu(x, alpha: float = 0.01):
    return jnp.where(x >= 0, x, alpha * x)


@register("softmax")
def softmax(x):
    return jax.nn.softmax(x, axis=-1)


@register("softplus")
def softplus(x):
    return jax.nn.softplus(x)


@register("softsign")
def softsign(x):
    return jax.nn.soft_sign(x)


@register("hardtanh")
def hardtanh(x):
    return jnp.clip(x, -1.0, 1.0)


@register("hardsigmoid")
def hardsigmoid(x):
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


@register("elu")
def elu(x):
    return jax.nn.elu(x)


@register("selu")
def selu(x):
    return jax.nn.selu(x)


@register("gelu")
def gelu(x):
    return jax.nn.gelu(x)


@register("swish")
@register("silu")
def swish(x):
    return jax.nn.silu(x)


@register("cube")
def cube(x):
    return x ** 3


@register("rationaltanh")
def rationaltanh(x):
    # 1.7159 * tanh_approx(2x/3), tanh_approx(y) = sign(y)(1 - 1/(1+|y|+y^2+1.41645 y^4))
    # — ND4J RationalTanh op semantics.
    y = 2.0 * x / 3.0
    a = jnp.abs(y)
    approx = jnp.sign(y) * (1.0 - 1.0 / (1.0 + a + y * y + 1.41645 * a ** 4))
    return 1.7159 * approx
