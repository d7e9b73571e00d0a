"""``evabyte`` decoder (EvaByte) as a ComputationGraphConfiguration: a
byte-level model whose attention is EVA (``nn/conf/eva``: exact inside a
window block, one pooled key/value row a chunk before it), with a gated
FFN, norms with a unit offset and a float32 residual stream.

    h <- h + Attn(N(h; g1))              the adds in float32 (fp32_skip_add)
    h <- h + FFN(N(h; g2))
    logits = N(h; gf) @ W_head           float32 (fp32_logits), untied

``N`` is RMSNorm with its gain stored less one (``norm_add_unit_offset``);
``Attn`` is ``EvaAttentionLayer``; ``FFN`` is ``GatedFFNLayer`` (SwiGLU).
Vertices of layer ``i``: ``l{i}_n1``, ``l{i}_attn``, ``l{i}_res1``,
``l{i}_n2``, ``l{i}_ffn``, ``l{i}_res2``; then ``final_norm`` and ``out``.

Served through the paged engine, which gives each EVA vertex its window
pages and summary pages and each lane's absolute position; the whole
sequence through ``net.output``. There is no dense streaming cache, so no
``generate()``.
"""

from __future__ import annotations

from typing import Optional

from ..nn.conf.builders import NeuralNetConfiguration
from ..nn.conf.eva import EvaAttentionLayer
from ..nn.conf.graph import ElementWiseVertex
from ..nn.conf.inputs import InputType
from ..nn.conf.layers import (EmbeddingSequenceLayer, GatedFFNLayer, RMSNorm,
                              RnnOutputLayer)


def evabyte_lm(vocab_size: int, *, n_layers: int, d_model: int, d_ff: int,
               n_heads: int, head_dim: int, window_size: int,
               chunk_size: int, rope_theta: float = 10000.0,
               norm_eps: float = 1e-5, seed: int = 42,
               dtype: str = "float32",
               max_cache_t: Optional[int] = None):
    """The graph of ``n_layers`` EVA layers, ids in (``[b, t]`` int32), a
    softmax over ``vocab_size`` out. ``max_cache_t`` is the longest
    sequence served. A serving model: ``dtype`` may be ``stored_bf16``; the
    updater is plain SGD at rate 0."""
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater("sgd").learning_rate(0.0).dtype(dtype)
          .graph_builder().add_inputs("in"))
    gb.add_layer("embed", EmbeddingSequenceLayer(
        n_in=vocab_size, n_out=d_model, activation="identity"), "in")
    prev = "embed"
    for i in range(n_layers):
        attn = EvaAttentionLayer(
            n_in=d_model, n_out=d_model, n_heads=n_heads, head_dim=head_dim,
            window_size=window_size, chunk_size=chunk_size,
            rope_theta=rope_theta, max_cache_t=max_cache_t,
            activation="identity")
        ffn = GatedFFNLayer(n_in=d_model, n_out=d_model, d_hidden=d_ff,
                            activation="identity")
        for half, (mixer, kind) in enumerate(((attn, "attn"), (ffn, "ffn"))):
            norm = f"l{i}_n{half + 1}"
            gb.add_layer(norm, RMSNorm(eps=norm_eps, unit_offset=True), prev)
            gb.add_layer(f"l{i}_{kind}", mixer, norm)
            gb.add_vertex(f"l{i}_res{half + 1}",
                          ElementWiseVertex(op="add", float32=True),
                          prev, f"l{i}_{kind}")
            prev = f"l{i}_res{half + 1}"
    gb.add_layer("final_norm", RMSNorm(eps=norm_eps, unit_offset=True), prev)
    gb.add_layer("out", RnnOutputLayer(
        n_in=d_model, n_out=vocab_size, has_bias=False, float32_logits=True,
        activation="softmax", loss="sparse_mcxent"), "final_norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(1))
    return gb.build()
