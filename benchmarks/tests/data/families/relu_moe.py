"""A second family, for the tests: this repo's own expert block,
``transformer_lm(..., moe_experts=E, moe_top_k=k)``. OPT's block with the
feed-forward pair replaced by ``nn/conf/moe.py``'s ``MoELayer``: a router
``[d, E]``, expert-stacked ``w1 [E, d, h]``, ``b1 [E, h]``, ``w2 [E, h, d]``,
``b2 [E, d]``, softmax gates renormalised over the top k, ReLU, dense
dispatch, a load-balancing loss added to the training loss.

It proves that a family which is not OPT arrives as files: no cell of
BENCHMARK.json uses it, and no file under ``benchmarks/lib`` knows it.
``families/opt.py`` says what a family file gives.
"""

from __future__ import annotations

ATTENTION = {             # leaf -> shape in terms of d, kind, (vertex, leaf)
    "ln1_g": ("d", "gain", ("ln1", "gamma")),
    "ln1_b": ("d", "bias", ("ln1", "beta")),
    "wqkv": ("d,3d", "matrix", ("attn", "Wqkv")),
    "wo": ("d,d", "matrix", ("attn", "Wo")),
    "bo": ("d", "bias", ("attn", "b")),
    "ln2_g": ("d", "gain", ("ln2", "gamma")),
    "ln2_b": ("d", "bias", ("ln2", "beta")),
}
EXPERTS = {               # all at the block's one ``moe`` vertex
    "router": ("d,E", "matrix"), "w1": ("E,d,h", "matrix"),
    "b1": ("E,h", "bias"), "w2": ("E,h,d", "matrix"), "b2": ("E,d", "bias"),
}
# The program's expert layer counts no routed (token, expert) pairs: under
# dense dispatch every token's necessary operations are its top k experts',
# which the sizes give. What a family lists here is read at the window's two
# edges all the same; this one takes the prompt tokens computed from it.
WANTS = [{"metric": "decode_tokens_total", "labels": {"phase": "prefill"},
          "stat": "value"}]


def build_conf(cfg: dict, seed: int, max_cache_t=None):
    from deeplearning4j_tpu.models import transformer_lm
    conf = transformer_lm(
        cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["expert_ffn_dim"], moe_experts=cfg["num_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        updater=cfg.get("updater", "sgd"),
        learning_rate=cfg.get("learning_rate", 0.0),
        seed=int(seed) & 0x7FFFFFFF, dtype=cfg["dtype"], input_ids=True,
        max_cache_t=max_cache_t)
    for i in range(cfg["num_hidden_layers"]):
        # the builder's default activation is a sigmoid (families/opt.py)
        conf.vertices[f"blk{i}_attn"].layer.activation = "identity"
        conf.vertices[f"blk{i}_moe"].layer.aux_weight = cfg["aux_weight"]
    return conf


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return {"d": d, "3d": 3 * d, "h": cfg["expert_ffn_dim"],
            "E": cfg["num_experts"]}


def leaf_shapes(cfg: dict) -> dict:
    dims, v = _dims(cfg), cfg["vocab_size"]
    d = dims["d"]
    out = {"embed": ((v, d), "matrix"), "lnf_g": ((d,), "gain"),
           "lnf_b": ((d,), "bias"), "head_w": ((d, v), "matrix"),
           "head_b": ((v,), "bias")}
    layer = {k: (spec, kind) for k, (spec, kind, _) in ATTENTION.items()}
    layer.update(EXPERTS)
    for i in range(cfg["num_hidden_layers"]):
        for k, (spec, kind) in layer.items():
            out[f"l{i}.{k}"] = (tuple(dims[s] for s in spec.split(",")), kind)
    return out


def program_names(cfg: dict) -> dict:
    out = {"embed": ("embed", "W"), "lnf_g": ("final_ln", "gamma"),
           "lnf_b": ("final_ln", "beta"), "head_w": ("out", "W"),
           "head_b": ("out", "b")}
    for i in range(cfg["num_hidden_layers"]):
        for k, (_, _, (vertex, leaf)) in ATTENTION.items():
            out[f"l{i}.{k}"] = (f"blk{i}_{vertex}", leaf)
        for k in EXPERTS:
            out[f"l{i}.{k}"] = (f"blk{i}_moe", k)
    return out


def expert_params(cfg: dict) -> float:
    """Parameters of the experts' matrix products that one token needs in
    one layer: its top k experts' pairs (the router is not among them)."""
    return (cfg["num_experts_per_tok"] * 2.0 * cfg["hidden_size"]
            * cfg["expert_ffn_dim"])


def matmul_params(cfg: dict) -> float:
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * (4.0 * d * d + d * cfg["num_experts"]
                                        + expert_params(cfg))
            + d * cfg["vocab_size"])


def attention_flops(cfg: dict, context: float) -> float:
    return cfg["num_hidden_layers"] * 4.0 * cfg["hidden_size"] * context


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * (2.0 * matmul_params(cfg)
                  + attention_flops(cfg, seq_len / 2.0))


def serve_flops(cfg: dict, work: dict):
    prompt_tokens = work["deltas"][0]
    if prompt_tokens is None:           # the program lacks the counter
        return None
    decoded = work["computed_tokens"] - prompt_tokens
    return (2.0 * matmul_params(cfg) * (prompt_tokens + decoded)
            + attention_flops(cfg, 1.0) * work["attended_keys"])


def attention_shape(cfg: dict, mix: dict) -> tuple:
    heads = cfg["num_attention_heads"]
    return (mix["batch"], mix["seq_len"], heads, cfg["hidden_size"] // heads)
