"""The ``nemotron_h`` family (``"model_type": "nemotron_h"``): the hybrid
decoder of ``models.nemotron_h.nemotron_h_lm``, served. One mixer a layer,
by the configuration's ``hybrid_override_pattern``: ``M`` Mamba-2, ``E``
LatentMoE, ``*`` grouped-query attention (``families/opt.py`` says what a
family file gives the harness).

The configuration's keys are the published ``config.json``'s. An expert
layer holds ``n_routed_experts`` experts from ``expert_offset`` on, of the
``n_routed_experts_published`` that its router scores (one chip's share of
an expert-parallel deployment; the two are equal where nothing is cut), and
``vocab_size`` rows of the embedding and columns of the head (one chip's
slice; ids, logits and sampling are over the slice).

Weights (``lib/weights.py`` draws by kind): matrices, the embedding, the
router and the expert stacks N(0, 0.02²); ``A_log``, ``dt_bias``, the
router's selection bias and the convolution's bias are ``bias`` (N(0,
0.02²): ``A`` near -1, ``dt`` near softplus of the projection); ``D``, the
norms' gains and the convolution's taps are ``gain`` (1 + N(0, 0.02²)). The
taps are a gain so that the convolution passes its input on at its own
size: as N(0, 0.02²) they would shrink ``x``, ``B`` and ``C`` to a few
hundredths and the carried SSM state to a thousandth of the skip term
``D·x``, and a program that dropped the state would read like a sound one.

The counts are what the algorithm needs (a multiply-add is two
operations): every token pays the dense products of its layers; an expert
layer's routed part is paid by the (token, expert) pair routed to an
expert HELD here, which the program counts
(``moe_routed_pairs_total{where="held"}``, in ``WANTS``); padding, tiles'
slack rows and the absent experts' part count nothing.
"""

from __future__ import annotations

MAMBA = {                 # leaf -> (shape in terms of ``_dims``, kind)
    "W_in": ("d,in", "matrix"), "conv_w": ("C,K", "gain"),
    "conv_b": ("C", "bias"), "dt_bias": ("H", "bias"), "A_log": ("H", "bias"),
    "D": ("H", "gain"), "norm_g": ("di", "gain"), "W_out": ("di,d", "matrix"),
}
ATTENTION = {"Wqkv": ("d,qkv", "matrix"), "Wo": ("d,d", "matrix")}
EXPERTS = {
    "router": ("d,Ep", "matrix"), "e_bias": ("Ep", "bias"),
    "W_down": ("d,L", "matrix"), "w1": ("Eh,L,F", "matrix"),
    "w2": ("Eh,F,L", "matrix"), "W_up": ("L,d", "matrix"),
    "ws1": ("d,Fs", "matrix"), "ws2": ("Fs,d", "matrix"),
}
KINDS = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}
WANTS = [{"metric": "moe_routed_pairs_total", "labels": {"where": "held"},
          "stat": "value"}]


def pattern(cfg: dict) -> str:
    p = cfg["hybrid_override_pattern"]
    if len(p) != cfg["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern has {len(p)} layers, "
                         f"num_hidden_layers says {cfg['num_hidden_layers']}")
    return p


def _dims(cfg: dict) -> dict:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    c = h * p + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if heads * cfg["head_dim"] != cfg["hidden_size"]:
        raise ValueError("the program's attention layer takes head_dim = "
                         "hidden_size / num_attention_heads")
    return {"d": cfg["hidden_size"], "H": h, "di": h * p, "C": c,
            "in": h * p + c + h, "K": cfg["conv_kernel"],
            "qkv": (heads + 2 * kv) * cfg["head_dim"],
            "Ep": cfg.get("n_routed_experts_published",
                          cfg["n_routed_experts"]),
            "Eh": cfg["n_routed_experts"], "L": cfg["moe_latent_size"],
            "F": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_shared_expert_intermediate_size"]}


def build_conf(cfg: dict, seed: int, max_cache_t=None):
    from deeplearning4j_tpu.models.nemotron_h import nemotron_h_lm
    dims = _dims(cfg)
    return nemotron_h_lm(
        cfg["vocab_size"], pattern=pattern(cfg), d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], mamba_groups=cfg["n_groups"],
        state_size=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        chunk_size=cfg["chunk_size"], n_experts=dims["Ep"],
        top_k=cfg["num_experts_per_tok"], d_latent=cfg["moe_latent_size"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        norm_eps=cfg["norm_eps"], seed=int(seed) & 0x7FFFFFFF,
        dtype=cfg["dtype"], max_cache_t=max_cache_t)


def leaf_shapes(cfg: dict) -> dict:
    dims = _dims(cfg)
    d, v = dims["d"], cfg["vocab_size"]
    out = {"embed": ((v, d), "matrix"), "final_g": ((d,), "gain"),
           "head_w": ((d, v), "matrix")}
    for i, kind in enumerate(pattern(cfg)):
        out[f"l{i}.g"] = ((d,), "gain")
        for k, (spec, leaf_kind) in KINDS[kind].items():
            out[f"l{i}.{k}"] = (tuple(dims[s] for s in spec.split(",")),
                                leaf_kind)
    return out


def program_names(cfg: dict) -> dict:
    out = {"embed": ("embed", "W"), "final_g": ("final_norm", "gamma"),
           "head_w": ("out", "W")}
    for i, kind in enumerate(pattern(cfg)):
        out[f"l{i}.g"] = (f"l{i}_norm", "gamma")
        for k in KINDS[kind]:
            out[f"l{i}.{k}"] = (f"l{i}_mix", k)
    return out


def dense_params(cfg: dict) -> float:
    """Parameters in the matrix products EVERY token takes: a Mamba
    layer's two projections, an attention layer's two, an expert layer's
    router, latent projections and shared expert, and the head. The
    embedding is a gather; the routed experts are paid by the pair."""
    dims, total = _dims(cfg), 0.0
    d = dims["d"]
    for kind in pattern(cfg):
        if kind == "M":
            total += d * dims["in"] + dims["di"] * d
        elif kind == "*":
            total += d * dims["qkv"] + d * d
        else:
            total += d * (dims["Ep"] + 2 * dims["L"] + 2 * dims["Fs"])
    return total + d * cfg["vocab_size"]


def pair_flops(cfg: dict) -> float:
    """One (token, expert) pair: the expert's two products."""
    return 2.0 * 2.0 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def grouped_ffn_work(cfg: dict, pairs: float, touched: float) -> tuple:
    """(operations, bytes) the expert layers' grouped product needs for
    ``pairs`` (token, expert) pairs routed to held experts of which
    ``touched`` (expert, layer, step) got at least one: a pair's two
    products; each touched expert's two matrices read once in the stored
    type, a pair's latent row read (stored type) and its result written
    (float32). Tiles' slack rows and a matrix read again for an expert's
    second tile count nothing."""
    width = 2 if cfg.get("param_dtype", "float32") == "bfloat16" else 4
    latent, hidden = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    return (pair_flops(cfg) * pairs,
            touched * 2.0 * latent * hidden * width
            + pairs * latent * (width + 4.0))


def scan_flops(cfg: dict) -> float:
    """One token's state update and read-out in every Mamba layer: the
    outer product and the decayed sum (two multiply-adds an element of the
    state) and the product with ``C`` (one)."""
    state = (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
             * cfg["ssm_state_size"])
    return pattern(cfg).count("M") * 6.0 * state


def attention_flops(cfg: dict) -> float:
    """One (token, key) pair in every attention layer: q·k and p·v."""
    return pattern(cfg).count("*") * 4.0 * cfg["hidden_size"]


def serve_flops(cfg: dict, work: dict):
    held_pairs = work["deltas"][0]
    if held_pairs is None:              # the program lacks the counter
        return None
    return ((2.0 * dense_params(cfg) + scan_flops(cfg))
            * work["computed_tokens"]
            + pair_flops(cfg) * held_pairs
            + attention_flops(cfg) * work["attended_keys"])
