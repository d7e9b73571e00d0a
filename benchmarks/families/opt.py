"""The OPT family (``"model_type": "opt"``): all that the harness knows of
this repo's plain decoder block, ``models.transformer.transformer_lm``.

A family file is found by the ``model_type`` of a configuration
(``families/<model_type>.py``) and gives the harness:

``build_conf(cfg, seed, max_cache_t)``   the program's configuration
``leaf_shapes(cfg)``                     flat name -> (shape, kind); kinds
                                         ``matrix``, ``bias``, ``gain``
``program_names(cfg)``                   flat name -> (vertex, leaf)
``WANTS``                                counters of the program whose deltas
                                         over the window ``serve_flops`` needs
``serve_flops(cfg, work)``               forward operations of a serving window
``train_flops_per_token(cfg, seq_len)``  forward + backward of one token
``attention_shape(cfg, mix)``            what one causal-attention call of a
                                         training step sees

The plain reference of a configuration is the file it names under
``reference``; it reads its sizes from the configuration it is handed and
imports nothing from here.

Weights: matrices and the embedding are N(0, 0.02²) as OPT initialises
them; biases and the LayerNorm offsets are N(0, 0.02²) and the gains 1 +
N(0, 0.02²), not 0 and 1, so that a path that drops one shows in the
comparison (``lib/weights.py`` draws them by ``kind``).

The counts are what the algorithm needs, not what a program happens to
execute: recomputation, padding and masked-out work count nothing. A
multiply-add is two operations.
"""

from __future__ import annotations

LAYER_SHAPES = {          # leaf -> (shape in terms of d, ff), kind
    "ln1_g": ("d", "gain"), "ln1_b": ("d", "bias"),
    "wqkv": ("d,3d", "matrix"), "wo": ("d,d", "matrix"), "bo": ("d", "bias"),
    "ln2_g": ("d", "gain"), "ln2_b": ("d", "bias"),
    "w1": ("d,ff", "matrix"), "b1": ("ff", "bias"),
    "w2": ("ff,d", "matrix"), "b2": ("d", "bias"),
}
LAYER_VERTICES = {        # leaf -> (vertex of block i, leaf there)
    "ln1_g": ("ln1", "gamma"), "ln1_b": ("ln1", "beta"),
    "wqkv": ("attn", "Wqkv"), "wo": ("attn", "Wo"), "bo": ("attn", "b"),
    "ln2_g": ("ln2", "gamma"), "ln2_b": ("ln2", "beta"),
    "w1": ("ff1", "W"), "b1": ("ff1", "b"),
    "w2": ("ff2", "W"), "b2": ("ff2", "b"),
}
WANTS: list = []          # sizes alone give a dense block's operations


def build_conf(cfg: dict, seed: int, max_cache_t=None):
    from deeplearning4j_tpu.models import transformer_lm
    conf = transformer_lm(
        cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["ffn_dim"], updater=cfg.get("updater", "sgd"),
        learning_rate=cfg.get("learning_rate", 0.0),
        seed=int(seed) & 0x7FFFFFFF, dtype=cfg["dtype"], input_ids=True,
        max_cache_t=max_cache_t)
    # transformer_lm() leaves the attention layer's activation to the
    # builder's default, a sigmoid (PERF.md, Open questions); OPT's block
    # has none after the output projection, so the configuration says so
    for i in range(cfg["num_hidden_layers"]):
        conf.vertices[f"blk{i}_attn"].layer.activation = "identity"
    return conf


def leaf_shapes(cfg: dict) -> dict:
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


def program_names(cfg: dict) -> dict:
    out = {"embed": ("embed", "W"), "lnf_g": ("final_ln", "gamma"),
           "lnf_b": ("final_ln", "beta"), "head_w": ("out", "W"),
           "head_b": ("out", "b")}
    for i in range(cfg["num_hidden_layers"]):
        for k, (vertex, leaf) in LAYER_VERTICES.items():
            out[f"l{i}.{k}"] = (f"blk{i}_{vertex}", leaf)
    return out


def matmul_params(cfg: dict) -> float:
    """Parameters that take part in a matrix product for every token:
    per layer Wqkv 3d², Wo d², the feed-forward pair 2·d·ff, and the d·V
    vocabulary head. The embedding is a gather and counts nothing."""
    d, ff = cfg["hidden_size"], cfg["ffn_dim"]
    return (cfg["num_hidden_layers"] * (4.0 * d * d + 2.0 * d * ff)
            + d * cfg["vocab_size"])


def attention_flops(cfg: dict, context: float) -> float:
    """Forward operations of one token's attention over ``context`` keys in
    every layer: q·kᵀ and p·v, 2·d multiply-adds each."""
    return cfg["num_hidden_layers"] * 4.0 * cfg["hidden_size"] * context


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward of one token in a causal LM trained at
    ``seq_len``: three times the forward (the backward is two matrix
    products for each forward one); the causal attention sees (T+1)/2 keys
    on average, counted as T/2 (copied from bench.py's
    ``_transformer_train_flops_per_token``)."""
    return 3.0 * (2.0 * matmul_params(cfg)
                  + attention_flops(cfg, seq_len / 2.0))


def serve_flops(cfg: dict, work: dict) -> float:
    """Forward operations of serving: ``work["computed_tokens"]`` tokens
    pushed through the matrices (prompt tokens taken from the prefix cache
    are not among them) and ``work["attended_keys"]`` (token, key) pairs,
    summed over the computed tokens, in one layer."""
    return (2.0 * matmul_params(cfg) * work["computed_tokens"]
            + attention_flops(cfg, 1.0) * work["attended_keys"])


def attention_shape(cfg: dict, mix: dict) -> tuple:
    """(batch, seq_len, heads, head size) of a training step's
    self-attention call: the shape ``flash_roofline`` counts over."""
    heads = cfg["num_attention_heads"]
    return (mix["batch"], mix["seq_len"], heads, cfg["hidden_size"] // heads)
