"""``solar_open2`` hybrid decoder (Solar Open 2) as a
ComputationGraphConfiguration: Kimi delta attention layers beside gated
grouped-query softmax layers with no positional term, each followed by a
layer of gated experts.

    h <- h + Mixer_l(RMSNorm(h; g1_l))       GQA where l is in ``gqa_layers``,
                                              Kimi delta attention elsewhere
    h <- h + Experts_l(RMSNorm(h; g2_l))
    logits = RMSNorm(h; g_f) @ W_head         untied, no bias anywhere

The GQA mixer is ``nn/conf/attention.SelfAttentionLayer`` with heads of
``head_dim`` (not ``d_model / n_heads``), ``n_kv_heads`` K/V heads, no
rotary term and an output gate ``sigmoid(u @ Wgate)`` before ``Wo``; the
KDA mixer is ``nn/conf/kda.KimiDeltaAttention``; the experts are
``nn/conf/moe.GatedMoELayer`` (sigmoid-scored routing, a shared expert, a
share of the experts held). Vertices of layer ``i``: ``l{i}_norm1``,
``l{i}_mix``, ``l{i}_res1``, ``l{i}_norm2``, ``l{i}_ffn``, ``l{i}_res2``;
then ``final_norm`` and ``out``.

Decode goes through the entry points of ``transformer_lm``:
``models.transformer.generate`` offline (the attention layers' streaming
K/V and the KDA layers' carries ride ``rnn_time_step``) and the paged
serving engine, whose walker gives each attention vertex its K/V pools and
each KDA vertex its row of the arena's per-lane state.
"""

from __future__ import annotations

from typing import Optional, Sequence

from ..nn.conf.attention import SelfAttentionLayer
from ..nn.conf.builders import NeuralNetConfiguration
from ..nn.conf.graph import ElementWiseVertex
from ..nn.conf.inputs import InputType
from ..nn.conf.kda import KimiDeltaAttention
from ..nn.conf.layers import EmbeddingSequenceLayer, RMSNorm, RnnOutputLayer
from ..nn.conf.moe import GatedMoELayer


def solar_open2_lm(vocab_size: int, *, n_layers: int,
                   gqa_layers: Sequence[int], d_model: int,
                   # gated grouped-query attention
                   n_heads: int, n_kv_heads: int, head_dim: int,
                   # Kimi delta attention
                   kda_heads: int, kda_head_dim: int, kda_gate_rank: int,
                   conv_kernel: int = 4,
                   # gated experts
                   n_experts: int = 8, top_k: int = 2, d_expert: int = 64,
                   d_shared: int = 64, routed_scale: float = 1.0,
                   experts_held: Optional[int] = None,
                   expert_offset: int = 0,
                   norm_eps: float = 1e-5, seed: int = 42,
                   dtype: str = "float32",
                   max_cache_t: Optional[int] = None):
    """The graph of ``n_layers`` layers, a GQA mixer at each index in
    ``gqa_layers`` (indices at or past ``n_layers`` are the layers left
    out) and a KDA mixer at every other, ids in (``[b, t]`` int32), a
    softmax over ``vocab_size`` out. ``experts_held``/``expert_offset`` make
    every expert layer hold that share of its ``n_experts`` (one chip of an
    expert-parallel deployment); ``max_cache_t`` arms the attention
    layers' streaming K/V cache. A serving model: ``dtype`` may be
    ``stored_bf16``; the updater is plain SGD at rate 0."""
    gqa = set(int(i) for i in gqa_layers)
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater("sgd").learning_rate(0.0).dtype(dtype)
          .graph_builder().add_inputs("in"))
    gb.add_layer("embed", EmbeddingSequenceLayer(
        n_in=vocab_size, n_out=d_model, activation="identity"), "in")
    prev = "embed"
    for i in range(n_layers):
        if i in gqa:
            mixer = SelfAttentionLayer(
                n_in=d_model, n_out=d_model, n_heads=n_heads,
                n_kv_heads=n_kv_heads, head_size=head_dim,
                output_gate=True, has_bias=False, causal=True,
                max_cache_t=max_cache_t, activation="identity")
        else:
            mixer = KimiDeltaAttention(
                n_in=d_model, n_out=d_model, n_heads=kda_heads,
                head_dim=kda_head_dim, gate_rank=kda_gate_rank,
                conv_kernel=conv_kernel,
                norm_eps=norm_eps, activation="identity")
        ffn = GatedMoELayer(
            n_in=d_model, n_out=d_model, d_hidden=d_expert,
            d_shared=d_shared, n_experts=n_experts,
            experts_held=experts_held, expert_offset=expert_offset,
            top_k=top_k, routed_scale=routed_scale, activation="identity")
        for half, (layer, kind) in enumerate(((mixer, "mix"), (ffn, "ffn"))):
            norm = f"l{i}_norm{half + 1}"
            gb.add_layer(norm, RMSNorm(eps=norm_eps), prev)
            gb.add_layer(f"l{i}_{kind}", layer, norm)
            gb.add_vertex(f"l{i}_res{half + 1}", ElementWiseVertex(op="add"),
                          prev, f"l{i}_{kind}")
            prev = f"l{i}_res{half + 1}"
    gb.add_layer("final_norm", RMSNorm(eps=norm_eps), prev)
    gb.add_layer("out", RnnOutputLayer(
        n_in=d_model, n_out=vocab_size, has_bias=False,
        activation="softmax", loss="sparse_mcxent"), "final_norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(1))
    return gb.build()
