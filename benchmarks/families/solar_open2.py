"""The ``solar_open2`` family (``"model_type": "solar_open2"``): the hybrid
decoder of ``models.solar_open2.solar_open2_lm``, served. Kimi delta
attention (KDA) layers and, at the indices of ``gqa_layers``, gated
grouped-query softmax layers with no positional term; every layer followed
by gated experts with a shared expert (``families/opt.py`` says what a
family file gives the harness).

The configuration's keys are the published ``config.json``'s (the KDA
widths in its nested ``linear_attn_config``). An expert layer holds
``n_routed_experts`` experts from ``expert_offset`` on, of the
``n_routed_experts_published`` that its router scores (one chip's share of
an expert-parallel deployment), and ``vocab_size`` rows of the embedding
and columns of the head (one chip's slice; ids, logits and sampling are
over the slice). ``gqa_layers`` keeps its published indices: those at or
past ``num_hidden_layers`` are layers left out. One size the config does
not give is the configuration's own key: ``kda_gate_rank``, the rank of the
decay's and the output gate's low-rank projections.

Weights (``lib/weights.py`` draws by kind): matrices, the embedding, the
router and the expert stacks N(0, 0.02²); ``A_log``, ``dt_bias`` and the
router's selection bias are ``bias`` (N(0, 0.02²)); the norms' gains, the
KDA output norm's gain and the convolution's taps are ``gain`` (1 + N(0,
0.02²)), so that the convolution passes its input on at its own size.
What these draws give the decay: ``A_log`` near 0 makes ``exp(A_log)``
near 1, and ``f @ W_fu`` (a product through rank 128 of N(0, 0.02²)
matrices) stays near 0 beside ``dt_bias``, so ``g`` is near ``-softplus(0)
= -0.69``: the state keeps about ``exp(-0.69)`` = a half of what it held a
token before, and forgets most of a position within a few tokens. The
chip's planted fault (every dispatch starting every KDA lane from zero)
still shows: the convolution's tail carries the last three positions
undecayed into every position's q, k and v, and the state's last few
writes are half and a quarter of what a sound lane reads, at every
dispatch's first positions (every position of a one-token step).

The counts are what the algorithm needs (a multiply-add is two
operations): every computed token pays the dense products of its layers
(KDA's projections, the low-rank gates included; GQA's four products; an
expert layer's router and shared expert); a KDA token pays its
recurrence in the RECURRENT form (decay, erase, write and read-out: 6 x D x
D a head), not the chunked form the prefill runs (whose intra-chunk
products are more); a GQA (token, key) pair pays q.k and p.v in every head;
an expert layer's routed part is paid by the (token, expert) pair routed to
an expert HELD here, which the program counts; the head is paid once a
decoded token and once a paged dispatch (the program computes it once a
lane and dispatch: at least that); padding, tiles' slack rows and the
absent experts' part count nothing.
"""

from __future__ import annotations

KDA = {                   # leaf -> (shape in terms of ``_dims``, kind)
    "W_in": ("d,in", "matrix"), "conv_w": ("C,K", "gain"),
    "A_log": ("H", "bias"), "dt_bias": ("HD", "bias"),
    "W_fu": ("r,HD", "matrix"), "W_gu": ("r,HD", "matrix"),
    "norm_g": ("D", "gain"), "W_o": ("HD,d", "matrix"),
}
GQA = {"Wqkv": ("d,qkv", "matrix"), "Wgate": ("d,q", "matrix"),
       "Wo": ("q,d", "matrix")}
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
    lin = cfg["linear_attn_config"]
    h, dk, r = lin["num_heads"], lin["head_dim"], cfg["kda_gate_rank"]
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    return {"d": cfg["hidden_size"], "H": h, "D": dk, "HD": h * dk, "r": r,
            "in": 3 * h * dk + h + 2 * r, "C": 3 * h * dk,
            "K": lin["short_conv_kernel_size"],
            "qkv": (heads + 2 * kv) * hd, "q": heads * hd,
            "Ep": cfg.get("n_routed_experts_published",
                          cfg["n_routed_experts"]),
            "Eh": cfg["n_routed_experts"], "F": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"]}


def _mixer(cfg: dict, i: int) -> dict:
    return GQA if i in cfg["gqa_layers"] else KDA


def build_conf(cfg: dict, seed: int, max_cache_t=None):
    from deeplearning4j_tpu.models.solar_open2 import solar_open2_lm
    lin = cfg["linear_attn_config"]
    if (cfg["tie_word_embeddings"] or cfg["use_rope"]
            or not cfg["use_gqa_gate"] or cfg["kda_use_full_proj"]
            or not cfg["kda_allow_neg_eigval"] or cfg["first_k_dense_replace"]
            or lin["num_kv_heads"] not in (None, lin["num_heads"])):
        raise ValueError("the program builds an untied head, GQA with no "
                         "rotary term and a gated output, KDA with low-rank "
                         "gates, beta in (0, 2) and a K/V head a query head, "
                         "and no dense layer, as the published configuration "
                         "has them")
    dims = _dims(cfg)
    return solar_open2_lm(
        cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
        gqa_layers=cfg["gqa_layers"], d_model=dims["d"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        kda_heads=dims["H"], kda_head_dim=dims["D"], kda_gate_rank=dims["r"],
        conv_kernel=dims["K"],
        n_experts=dims["Ep"], top_k=cfg["num_experts_per_tok"],
        d_expert=dims["F"], d_shared=dims["Fs"],
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
        out[f"l{i}.g1"] = ((d,), "gain")
        out[f"l{i}.g2"] = ((d,), "gain")
        for k, (spec, kind) in {**_mixer(cfg, i), **EXPERTS}.items():
            out[f"l{i}.{k}"] = (tuple(dims[s] for s in spec.split(",")), kind)
    return out


def program_names(cfg: dict) -> dict:
    out = {"embed": ("embed", "W"), "final_g": ("final_norm", "gamma"),
           "head_w": ("out", "W")}
    for i in range(cfg["num_hidden_layers"]):
        out[f"l{i}.g1"] = (f"l{i}_norm1", "gamma")
        out[f"l{i}.g2"] = (f"l{i}_norm2", "gamma")
        for k in _mixer(cfg, i):
            out[f"l{i}.{k}"] = (f"l{i}_mix", k)
        for k in EXPERTS:
            out[f"l{i}.{k}"] = (f"l{i}_ffn", k)
    return out


def layer_kinds(cfg: dict) -> str:
    """``*`` for a GQA layer, ``K`` for a KDA layer, in order."""
    return "".join("*" if i in cfg["gqa_layers"] else "K"
                   for i in range(cfg["num_hidden_layers"]))


def dense_params(cfg: dict) -> float:
    """Parameters in the matrix products EVERY computed token takes: a KDA
    layer's projections (the low-rank gates' both halves), a GQA layer's
    four, an expert layer's router and shared expert. The embedding is a
    gather, the routed experts are paid by the pair and the head by the
    row."""
    dims, total = _dims(cfg), 0.0
    d = dims["d"]
    for kind in layer_kinds(cfg):
        if kind == "K":
            total += (d * dims["in"] + 2 * dims["r"] * dims["HD"]
                      + dims["HD"] * d)
        else:
            total += d * dims["qkv"] + 2 * d * dims["q"]
        total += d * (dims["Ep"] + 3 * dims["Fs"])
    return total


def recurrence_flops(cfg: dict) -> float:
    """One token's delta rule in every KDA layer, in its recurrent form:
    the decay, the key's read of the state, the erase-and-write and the
    query's read-out, about six operations an element of each head's D x D
    state."""
    dims = _dims(cfg)
    return layer_kinds(cfg).count("K") * 6.0 * dims["H"] * dims["D"] ** 2


def attention_flops(cfg: dict) -> float:
    """One (token, key) pair in every GQA layer: q.k and p.v in each
    head."""
    return (layer_kinds(cfg).count("*") * 4.0 * cfg["num_attention_heads"]
            * cfg["head_dim"])


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


def serve_flops(cfg: dict, work: dict):
    held_pairs, decoded, paged = work["deltas"]
    if held_pairs is None:              # the program lacks the counter
        return None
    head_rows = (decoded or 0.0) + (paged or 0.0)
    return ((2.0 * dense_params(cfg) + recurrence_flops(cfg))
            * work["computed_tokens"]
            + pair_flops(cfg) * held_pairs
            + attention_flops(cfg) * work["attended_keys"]
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_rows)
