"""The ``pangu_ultra_moe`` family (``"model_type": "pangu_ultra_moe"``): the
decoder of ``models.pangu.pangu_ultra_moe_lm``, served. Latent attention
(MLA) with a rotary term, four RMSNorm gains a layer (``sandwich_norm``),
``first_k_dense_replace`` leading layers with a dense gated FFN and then
layers of gated experts with a shared expert (``families/opt.py`` says what
a family file gives the harness).

The configuration's keys are the published ``config.json``'s. An expert
layer holds ``n_routed_experts`` experts from ``expert_offset`` on, of the
``n_routed_experts_published`` that its router scores (one chip's share of
an expert-parallel deployment), and ``vocab_size`` rows of the embedding and
columns of the head (one chip's slice; ids, logits and sampling are over
the slice).

Weights (``lib/weights.py`` draws by kind): matrices, the embedding, the
router and the expert stacks N(0, 0.02²); the router's selection bias is a
``bias`` (N(0, 0.02²)); the four gains of a layer, both latents' gains and
the final gain are ``gain`` (1 + N(0, 0.02²)).

The counts are what the algorithm needs (a multiply-add is two operations):
every computed token pays the dense products of its layers, with the
attention counted in its TEXTBOOK form (a token's keys and values expanded
once, then ``H x 2 x (192 + 128)`` operations a (token, key) pair), which is
fewer than the absorbed form the program runs (``H x 2 x (576 + 512)``), so
that ``serve_mfu`` errs low; an expert layer's routed part is paid by the
(token, expert) pair routed to an expert HELD here, which the program
counts; the head is paid once a decoded token and once a paged dispatch
(the program computes it once a lane and dispatch: at least that);
padding, tiles' slack rows and the absent experts' part count nothing.
"""

from __future__ import annotations

ATTENTION = {             # leaf -> (shape in terms of ``_dims``, kind)
    "W_qa": ("d,qr", "matrix"), "q_norm_g": ("qr", "gain"),
    "W_qb": ("qr,Hqk", "matrix"), "W_kva": ("d,row", "matrix"),
    "kv_norm_g": ("kvr", "gain"), "W_kvb": ("kvr,Hkv", "matrix"),
    "W_o": ("Hv,d", "matrix"),
}
DENSE = {"W_gate": ("d,ff", "matrix"), "W_up": ("d,ff", "matrix"),
         "W_down": ("ff,d", "matrix")}
EXPERTS = {
    "router": ("d,Ep", "matrix"), "e_bias": ("Ep", "bias"),
    "wg": ("Eh,d,F", "matrix"), "wu": ("Eh,d,F", "matrix"),
    "wd": ("Eh,F,d", "matrix"), "sg": ("d,Fs", "matrix"),
    "su": ("d,Fs", "matrix"), "sd": ("Fs,d", "matrix"),
}
WANTS = [{"metric": "moe_routed_pairs_total", "labels": {"where": "held"},
          "stat": "value"},
         {"metric": "decode_tokens_total", "labels": {"phase": "decode"},
          "stat": "value"},
         {"metric": "decode_dispatches_total", "labels": {"kind": "paged"},
          "stat": "value"}]


def _dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return {"d": cfg["hidden_size"], "qr": cfg["q_lora_rank"],
            "kvr": cfg["kv_lora_rank"], "row": cfg["kv_lora_rank"] + rope,
            "Hqk": h * (nope + rope), "Hkv": h * (nope + v), "Hv": h * v,
            "ff": cfg["intermediate_size"],
            "Ep": cfg.get("n_routed_experts_published",
                          cfg["n_routed_experts"]),
            "Eh": cfg["n_routed_experts"], "F": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"]}


def _ffn(cfg: dict, i: int) -> dict:
    return DENSE if i < cfg["first_k_dense_replace"] else EXPERTS


def build_conf(cfg: dict, seed: int, max_cache_t=None):
    from deeplearning4j_tpu.models.pangu import pangu_ultra_moe_lm
    if not cfg["sandwich_norm"] or cfg["tie_word_embeddings"]:
        raise ValueError("the program builds sandwich norms and an untied "
                         "head, as the published configuration has them")
    dims = _dims(cfg)
    return pangu_ultra_moe_lm(
        cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        n_heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), n_experts=dims["Ep"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], d_shared=dims["Fs"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        norm_eps=cfg["rms_norm_eps"], seed=int(seed) & 0x7FFFFFFF,
        dtype=cfg["dtype"], max_cache_t=max_cache_t)


def leaf_shapes(cfg: dict) -> dict:
    dims = _dims(cfg)
    d, v = dims["d"], cfg["vocab_size"]
    out = {"embed": ((v, d), "matrix"), "final_g": ((d,), "gain"),
           "head_w": ((d, v), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        for n in range(1, 5):
            out[f"l{i}.g{n}"] = ((d,), "gain")
        for k, (spec, kind) in {**ATTENTION, **_ffn(cfg, i)}.items():
            out[f"l{i}.{k}"] = (tuple(dims[s] for s in spec.split(",")), kind)
    return out


def program_names(cfg: dict) -> dict:
    out = {"embed": ("embed", "W"), "final_g": ("final_norm", "gamma"),
           "head_w": ("out", "W")}
    for i in range(cfg["num_hidden_layers"]):
        for n in range(1, 5):
            out[f"l{i}.g{n}"] = (f"l{i}_n{n}", "gamma")
        for k in ATTENTION:
            out[f"l{i}.{k}"] = (f"l{i}_attn", k)
        for k in _ffn(cfg, i):
            out[f"l{i}.{k}"] = (f"l{i}_ffn", k)
    return out


def attention_params(cfg: dict) -> float:
    """One layer's attention matrices: every token takes each once (the
    textbook form expands a token's keys and values once)."""
    dims = _dims(cfg)
    return (dims["d"] * dims["qr"] + dims["qr"] * dims["Hqk"]
            + dims["d"] * dims["row"] + dims["kvr"] * dims["Hkv"]
            + dims["Hv"] * dims["d"])


def dense_params(cfg: dict) -> float:
    """Parameters in the matrix products EVERY computed token takes: the
    attention's, a dense layer's gated FFN, an expert layer's router and
    shared expert. The embedding is a gather, the routed experts are paid
    by the pair and the head by the row."""
    dims = _dims(cfg)
    d, dense = dims["d"], cfg["first_k_dense_replace"]
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * 3 * d * dims["ff"]
            + (cfg["num_hidden_layers"] - dense)
            * d * (dims["Ep"] + 3 * dims["Fs"]))


def pair_flops(cfg: dict) -> float:
    """One (token, expert) pair: the gated expert's three products."""
    return 3.0 * 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def grouped_ffn_work(cfg: dict, pairs: float, touched: float) -> tuple:
    """(operations, bytes) the expert layers' grouped product needs for
    ``pairs`` (token, expert) pairs routed to held experts of which
    ``touched`` (expert, layer, step) got at least one: a pair's three
    products; each touched expert's three matrices read once in the stored
    type; a pair's row read (stored type) and its result written
    (float32). Tiles' slack rows and matrices read again for an expert's
    second tile count nothing."""
    width = 2 if cfg.get("param_dtype", "float32") == "bfloat16" else 4
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (pair_flops(cfg) * pairs,
            touched * 3.0 * d * f * width + pairs * d * (width + 4.0))


def attention_flops(cfg: dict) -> float:
    """One (token, key) pair in every layer, the textbook way: q·k over
    ``nope + rope`` and p·v over ``v`` in each head."""
    return (cfg["num_hidden_layers"] * cfg["num_attention_heads"] * 2.0
            * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
               + cfg["v_head_dim"]))


def serve_flops(cfg: dict, work: dict):
    held_pairs, decoded, paged = work["deltas"]
    if held_pairs is None:              # the program lacks the counter
        return None
    head_rows = (decoded or 0.0) + (paged or 0.0)
    return (2.0 * dense_params(cfg) * work["computed_tokens"]
            + pair_flops(cfg) * held_pairs
            + attention_flops(cfg) * work["attended_keys"]
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_rows)
