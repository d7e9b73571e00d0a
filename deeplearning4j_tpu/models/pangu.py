"""``pangu_ultra_moe`` decoder (openPangu-Ultra-MoE) as a
ComputationGraphConfiguration: latent attention with a rotary term,
sandwich norms, leading dense gated layers, then gated expert layers.

    h <- h + N(Attn(N(h; g1)); g2)            four gains a layer
    h <- h + N(FFN(N(h; g3)); g4)             (``sandwich_norm``)
    logits = N(h; gf) @ W_head                untied, no bias anywhere

``N`` is RMSNorm; ``Attn`` is ``nn/conf/mla.MLAttentionLayer``; ``FFN`` is
``nn/conf/layers.GatedFFNLayer`` in the first ``first_k_dense`` layers and
``nn/conf/moe.GatedMoELayer`` (sigmoid-scored routing, a shared expert, a
share of the experts held) in the rest. Vertices of layer ``i``:
``l{i}_n1``, ``l{i}_attn``, ``l{i}_n2``, ``l{i}_res1``, ``l{i}_n3``,
``l{i}_ffn``, ``l{i}_n4``, ``l{i}_res2``; then ``final_norm`` and ``out``.

Decode goes through the entry points of ``transformer_lm``:
``models.transformer.generate`` offline (the latent cache rides
``rnn_time_step``) and the paged serving engine, whose walker gives each
attention vertex ONE latent pool and each lane's absolute position.
"""

from __future__ import annotations

from typing import Optional

from ..nn.conf.builders import NeuralNetConfiguration
from ..nn.conf.graph import ElementWiseVertex
from ..nn.conf.inputs import InputType
from ..nn.conf.layers import (EmbeddingSequenceLayer, GatedFFNLayer, RMSNorm,
                              RnnOutputLayer)
from ..nn.conf.mla import MLAttentionLayer
from ..nn.conf.moe import GatedMoELayer


def pangu_ultra_moe_lm(vocab_size: int, *, n_layers: int, first_k_dense: int,
                       d_model: int, d_ff: int,
                       # latent attention
                       n_heads: int, q_rank: int, kv_rank: int,
                       nope_dim: int, rope_dim: int, v_dim: int,
                       rope_theta: float = 10000.0,
                       # gated experts
                       n_experts: int = 8, top_k: int = 2,
                       d_expert: int = 64, d_shared: int = 64,
                       routed_scale: float = 1.0,
                       experts_held: Optional[int] = None,
                       expert_offset: int = 0,
                       norm_eps: float = 1e-5, seed: int = 42,
                       dtype: str = "float32",
                       max_cache_t: Optional[int] = None):
    """The graph of ``n_layers`` layers, the first ``first_k_dense`` with a
    dense gated FFN of ``d_ff``, ids in (``[b, t]`` int32), a softmax over
    ``vocab_size`` out. ``experts_held``/``expert_offset`` make every expert
    layer hold that share of its ``n_experts`` (one chip of an
    expert-parallel deployment); ``max_cache_t`` arms the attention
    layers' streaming cache. A serving model: ``dtype`` may be
    ``stored_bf16``; the updater is plain SGD at rate 0."""
    if not 0 <= first_k_dense <= n_layers:
        raise ValueError(f"first_k_dense={first_k_dense} of {n_layers} layers")
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater("sgd").learning_rate(0.0).dtype(dtype)
          .graph_builder().add_inputs("in"))
    gb.add_layer("embed", EmbeddingSequenceLayer(
        n_in=vocab_size, n_out=d_model, activation="identity"), "in")
    prev = "embed"
    for i in range(n_layers):
        attn = MLAttentionLayer(
            n_in=d_model, n_out=d_model, n_heads=n_heads, q_rank=q_rank,
            kv_rank=kv_rank, nope_dim=nope_dim, rope_dim=rope_dim,
            v_dim=v_dim, rope_theta=rope_theta, norm_eps=norm_eps,
            max_cache_t=max_cache_t, activation="identity")
        if i < first_k_dense:
            ffn = GatedFFNLayer(n_in=d_model, n_out=d_model, d_hidden=d_ff,
                                activation="identity")
        else:
            ffn = GatedMoELayer(
                n_in=d_model, n_out=d_model, d_hidden=d_expert,
                d_shared=d_shared, n_experts=n_experts,
                experts_held=experts_held, expert_offset=expert_offset,
                top_k=top_k, routed_scale=routed_scale,
                activation="identity")
        for half, (mixer, kind) in enumerate(((attn, "attn"), (ffn, "ffn"))):
            pre, post = f"l{i}_n{2 * half + 1}", f"l{i}_n{2 * half + 2}"
            gb.add_layer(pre, RMSNorm(eps=norm_eps), prev)
            gb.add_layer(f"l{i}_{kind}", mixer, pre)
            gb.add_layer(post, RMSNorm(eps=norm_eps), f"l{i}_{kind}")
            gb.add_vertex(f"l{i}_res{half + 1}", ElementWiseVertex(op="add"),
                          prev, post)
            prev = f"l{i}_res{half + 1}"
    gb.add_layer("final_norm", RMSNorm(eps=norm_eps), prev)
    gb.add_layer("out", RnnOutputLayer(
        n_in=d_model, n_out=vocab_size, has_bias=False,
        activation="softmax", loss="sparse_mcxent"), "final_norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(1))
    return gb.build()
