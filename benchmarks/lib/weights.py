"""Weights from the seed, made on the device in one jitted call.

The benchmark owns the weights: the program under test is handed them (in
the layout its layers expect, float32 as ``mixed_bf16`` keeps them) and the
plain reference makes the same ones again from the same seed after the
window. Matrices and the embedding are N(0, 0.02²) as OPT initialises them;
biases and the LayerNorm offsets are N(0, 0.02²) and the gains 1 + N(0,
0.02²), not 0 and 1, so that a path that drops one shows in the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02
LAYER_SHAPES = {          # leaf -> (shape in terms of d, ff), kind
    "ln1_g": ("d", "gain"), "ln1_b": ("d", "bias"),
    "wqkv": ("d,3d", "matrix"), "wo": ("d,d", "matrix"), "bo": ("d", "bias"),
    "ln2_g": ("d", "gain"), "ln2_b": ("d", "bias"),
    "w1": ("d,ff", "matrix"), "b1": ("ff", "bias"),
    "w2": ("ff,d", "matrix"), "b2": ("d", "bias"),
}


def leaf_shapes(cfg: dict) -> dict:
    """Flat name -> (shape, kind) for a configuration file's sizes."""
    d, ff, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    dims = {"d": d, "3d": 3 * d, "ff": ff}
    out = {"embed": ((v, d), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        for k, (spec, kind) in LAYER_SHAPES.items():
            out[f"l{i}.{k}"] = (tuple(dims[s] for s in spec.split(",")), kind)
    out["lnf_g"] = ((d,), "gain")
    out["lnf_b"] = ((d,), "bias")
    out["head_w"] = ((d, v), "matrix")
    out["head_b"] = ((v,), "bias")
    return out


def _make(shapes: dict, words):
    # the seed may exceed 32 signed bits: fold it in as two words
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(0), words[0]), words[1])
    out = {}
    for n, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        x = STD * jax.random.normal(jax.random.fold_in(key, n), shape,
                                    jnp.float32)
        out[name] = 1.0 + x if kind == "gain" else x
    return out


@functools.lru_cache(maxsize=None)
def _jitted(shapes_key: tuple):
    shapes = dict(shapes_key)

    def change_norms(words, now):
        start = _make(shapes, words)
        return {k: jnp.sqrt(jnp.sum(jnp.square(now[k] - start[k])))
                for k in start}

    return (jax.jit(functools.partial(_make, shapes)),
            jax.jit(change_norms))


def _seed_words(seed: int):
    seed = int(seed)
    return jnp.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       jnp.uint32)


def make_weights(cfg: dict, seed: int) -> dict:
    """The flat dict of float32 device arrays for ``cfg`` and ``seed``."""
    make, _ = _jitted(tuple(sorted(leaf_shapes(cfg).items())))
    return make(_seed_words(seed))


@jax.jit
def leaf_norms(flat: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


def change_norms(cfg: dict, seed: int, now: dict) -> dict:
    """Norm, leaf by leaf, of ``now`` minus the weights of ``seed``, which
    are drawn again inside the same program, so that no second copy of the
    model is ever held."""
    _, change = _jitted(tuple(sorted(leaf_shapes(cfg).items())))
    return change(_seed_words(seed), now)


# -- the program's layout ---------------------------------------------------

def program_names(cfg: dict) -> dict:
    """Flat name -> (vertex, leaf) of ``models.transformer.transformer_lm``."""
    out = {"embed": ("embed", "W"), "lnf_g": ("final_ln", "gamma"),
           "lnf_b": ("final_ln", "beta"), "head_w": ("out", "W"),
           "head_b": ("out", "b")}
    per_layer = {"ln1_g": ("ln1", "gamma"), "ln1_b": ("ln1", "beta"),
                 "wqkv": ("attn", "Wqkv"), "wo": ("attn", "Wo"),
                 "bo": ("attn", "b"), "ln2_g": ("ln2", "gamma"),
                 "ln2_b": ("ln2", "beta"), "w1": ("ff1", "W"),
                 "b1": ("ff1", "b"), "w2": ("ff2", "W"), "b2": ("ff2", "b")}
    for i in range(cfg["num_hidden_layers"]):
        for k, (vertex, leaf) in per_layer.items():
            out[f"l{i}.{k}"] = (f"blk{i}_{vertex}", leaf)
    return out


def to_program(cfg: dict, flat: dict, like: dict) -> dict:
    """Lay the flat dict out as the program's parameter tree ``like``
    (vertex -> leaf -> array); every leaf of ``like`` must be covered and
    agree in shape."""
    tree = {vertex: {} for vertex in like}
    for name, (vertex, leaf) in program_names(cfg).items():
        want = tuple(like[vertex][leaf].shape)
        if tuple(flat[name].shape) != want:
            raise ValueError(f"{name}: made {flat[name].shape}, the program "
                             f"holds {vertex}.{leaf} {want}")
        tree[vertex][leaf] = flat[name]
    for vertex, leaves in like.items():
        missing = set(leaves) - set(tree[vertex])
        if missing:
            raise ValueError(f"no weight made for {vertex}.{sorted(missing)}")
    return tree


def from_program(cfg: dict, tree: dict) -> dict:
    """The program's tree (parameters, or a like-shaped Adam moment) as the
    flat dict."""
    return {name: tree[vertex][leaf]
            for name, (vertex, leaf) in program_names(cfg).items()}
