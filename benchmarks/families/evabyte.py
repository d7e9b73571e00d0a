"""The ``evabyte`` family (``"model_type": "evabyte"``): the decoder of
``models.evabyte.evabyte_lm``, served. EVA attention (exact inside a
``window_size`` block, one pooled key/value row a ``chunk_size`` chunk
before it), a gated FFN, RMSNorm with a unit offset, a float32 residual
stream and a float32 head (``families/opt.py`` says what a family file gives
the harness).

The configuration's keys are the published ``config.json``'s; a head is
``hidden_size / num_attention_heads`` wide (the config gives no
``head_dim``).

Weights (``lib/weights.py`` draws by kind): matrices, the embedding and
the two pooling vectors a head N(0, 0.02²); the norms' gains, stored less
one (``norm_add_unit_offset``), are ``bias`` (N(0, 0.02²)), so a gain is 1 +
N(0, 0.02²) as in the other families.

The counts are what the algorithm needs (a multiply-add is two
operations): every computed token pays its layers' dense products; the
attention is paid by the rows a softmax takes (``eva_attended_rows_total``,
counted by the engine for every written position: its own window's rows up
to it and the summary rows before that window), ``2 x 2 x H x D`` a (token,
row) pair and layer; pooling a chunk into its summary pair costs ``2 x 2 x
C x H x D`` twice, paid by the summary rows written; the head is paid once a
decoded token and once a paged dispatch. Padding counts nothing.
"""

from __future__ import annotations

ATTENTION = {             # leaf -> (shape in terms of ``_dims``, kind)
    "W_q": ("d,hd", "matrix"), "W_k": ("d,hd", "matrix"),
    "W_v": ("d,hd", "matrix"), "W_o": ("hd,d", "matrix"),
    "phi": ("H,D", "matrix"), "mu": ("H,D", "matrix"),
}
FFN = {"W_gate": ("d,ff", "matrix"), "W_up": ("d,ff", "matrix"),
       "W_down": ("ff,d", "matrix")}
WANTS = [{"metric": "eva_attended_rows_total", "stat": "value"},
         {"metric": "eva_summary_rows_written_total", "stat": "value"},
         {"metric": "decode_tokens_total", "labels": {"phase": "decode"},
          "stat": "value"},
         {"metric": "decode_dispatches_total", "labels": {"kind": "paged"},
          "stat": "value"}]


def _dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    return {"d": d, "H": h, "D": d // h, "hd": d,
            "ff": cfg["intermediate_size"]}


def build_conf(cfg: dict, seed: int, max_cache_t=None):
    from deeplearning4j_tpu.models.evabyte import evabyte_lm
    if (cfg["tie_word_embeddings"] or cfg["attention_bias"]
            or not cfg["norm_add_unit_offset"] or not cfg["fp32_skip_add"]
            or not cfg["fp32_logits"]
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]):
        raise ValueError("the program builds an untied head, no attention "
                         "bias, unit-offset norms, a float32 residual stream "
                         "and head, one K/V head a query head, as the "
                         "published configuration has them")
    dims = _dims(cfg)
    return evabyte_lm(
        cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
        d_model=dims["d"], d_ff=dims["ff"], n_heads=dims["H"],
        head_dim=dims["D"], window_size=cfg["window_size"],
        chunk_size=cfg["chunk_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], seed=int(seed) & 0x7FFFFFFF,
        dtype=cfg["dtype"], max_cache_t=max_cache_t)


def leaf_shapes(cfg: dict) -> dict:
    dims = _dims(cfg)
    d, v = dims["d"], cfg["vocab_size"]
    out = {"embed": ((v, d), "matrix"), "final_g": ((d,), "bias"),
           "head_w": ((d, v), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        for n in (1, 2):
            out[f"l{i}.g{n}"] = ((d,), "bias")
        for k, (spec, kind) in {**ATTENTION, **FFN}.items():
            out[f"l{i}.{k}"] = (tuple(dims[s] for s in spec.split(",")), kind)
    return out


def program_names(cfg: dict) -> dict:
    out = {"embed": ("embed", "W"), "final_g": ("final_norm", "gamma"),
           "head_w": ("out", "W")}
    for i in range(cfg["num_hidden_layers"]):
        for n in (1, 2):
            out[f"l{i}.g{n}"] = (f"l{i}_n{n}", "gamma")
        for k in ATTENTION:
            out[f"l{i}.{k}"] = (f"l{i}_attn", k)
        for k in FFN:
            out[f"l{i}.{k}"] = (f"l{i}_ffn", k)
    return out


def dense_params(cfg: dict) -> float:
    """Parameters in the matrix products every computed token takes: the
    four projections and the gated FFN of each layer (the embedding is a
    gather, the head is paid by the row)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * d * d + 3 * d * ff)


def serve_flops(cfg: dict, work: dict):
    attended, summaries, decoded, paged = work["deltas"]
    if attended is None:                # the program lacks the counter
        return None
    layers, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    head_rows = (decoded or 0.0) + (paged or 0.0)
    return (2.0 * dense_params(cfg) * work["computed_tokens"]
            + layers * 4.0 * d * attended
            + 2.0 * 2.0 * 2.0 * cfg["chunk_size"] * d * (summaries or 0.0)
            + 2.0 * d * cfg["vocab_size"] * head_rows)
