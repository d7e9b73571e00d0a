"""Weights from the seed, made on the device in one jitted call.

The benchmark owns the weights: the program under test is handed them (in
the layout its layers expect) and the plain reference makes the same ones
again from the same seed after the window. Which leaves there are, their
shapes and kinds, and where each sits in the program's tree are the
configuration's family's to say (``families/<model_type>.py``:
``leaf_shapes``, ``program_names``). A ``matrix`` or ``bias`` is N(0,
0.02²) and a ``gain`` 1 + N(0, 0.02²).

Each leaf is drawn in float32 and rounded once, inside the jitted draw, to
the type the configuration stores its parameters in (``param_dtype``,
``float32`` where the file does not say): a model whose float32 copy would
not fit the chip is never held as one. The program and the reference get
the same rounded values; the reference computes on them in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def _shapes_key(family, cfg: dict) -> tuple:
    return (tuple(sorted(family.leaf_shapes(cfg).items())),
            cfg.get("param_dtype", "float32"))


def _make(shapes: dict, dtype, words):
    # the seed may exceed 32 signed bits: fold it in as two words
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), words[0]), words[1])
    out = {}
    for n, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        x = STD * jax.random.normal(jax.random.fold_in(key, n), shape,
                                    jnp.float32)
        out[name] = (1.0 + x if kind == "gain" else x).astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _jitted(shapes_key: tuple):
    shapes, dtype = dict(shapes_key[0]), jnp.dtype(shapes_key[1])

    def change_norms(words, now):
        start = _make(shapes, dtype, words)
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            now[k].astype(jnp.float32) - start[k].astype(jnp.float32))))
            for k in start}

    return (jax.jit(functools.partial(_make, shapes, dtype)),
            jax.jit(change_norms))


def _seed_words(seed: int):
    seed = int(seed)
    return jnp.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       jnp.uint32)


def make_weights(family, cfg: dict, seed: int) -> dict:
    """The flat dict of device arrays for ``cfg`` and ``seed``, each in the
    type the configuration stores it in."""
    make, _ = _jitted(_shapes_key(family, cfg))
    return make(_seed_words(seed))


@jax.jit
def leaf_norms(flat: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


def change_norms(family, cfg: dict, seed: int, now: dict) -> dict:
    """Norm, leaf by leaf, of ``now`` minus the weights of ``seed``, which
    are drawn again inside the same program, so that no second copy of the
    model is ever held."""
    _, change = _jitted(_shapes_key(family, cfg))
    return change(_seed_words(seed), now)


# -- the program's layout ---------------------------------------------------

def to_program(family, cfg: dict, flat: dict, like: dict) -> dict:
    """Lay the flat dict out as the program's parameter tree ``like``
    (vertex -> leaf -> array); every leaf of ``like`` must be covered and
    agree in shape and in type."""
    tree = {vertex: {} for vertex in like}
    for name, (vertex, leaf) in family.program_names(cfg).items():
        made = (tuple(flat[name].shape), jnp.dtype(flat[name].dtype))
        want = (tuple(like[vertex][leaf].shape),
                jnp.dtype(like[vertex][leaf].dtype))
        if made != want:
            raise ValueError(f"{name}: made {made[1]}{list(made[0])}, the "
                             f"program holds {vertex}.{leaf} as "
                             f"{want[1]}{list(want[0])}")
        tree[vertex][leaf] = flat[name]
    for vertex, leaves in like.items():
        missing = set(leaves) - set(tree[vertex])
        if missing:
            raise ValueError(f"no weight made for {vertex}.{sorted(missing)}")
    return tree


def from_program(family, cfg: dict, tree: dict) -> dict:
    """The program's tree (parameters, or a like-shaped Adam moment) as the
    flat dict."""
    return {name: tree[vertex][leaf]
            for name, (vertex, leaf) in family.program_names(cfg).items()}
