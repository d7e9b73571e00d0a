"""Hybrid state-space / attention / expert decoder (``nemotron_h``) as a
ComputationGraphConfiguration.

Each layer of the published pattern string is ONE mixer with its own
pre-norm and residual, where a transformer block pairs two:

    h <- h + Mixer_l(RMSNorm(h; g_l))      for each character of the pattern
    logits = RMSNorm(h; g_f) @ W_head      (untied, no bias)

``M`` is a Mamba-2 mixer (``nn/conf/ssm.Mamba2Mixer``), ``*`` causal
grouped-query attention without a rotary term
(``nn/conf/attention.SelfAttentionLayer``), ``E`` a LatentMoE layer
(``nn/conf/moe.LatentMoELayer``), which may hold a share of the experts
its router scores. No bias anywhere but the convolution's. Vertices of
layer ``i``: ``l{i}_norm``, ``l{i}_mix``, ``l{i}_res``; then ``final_norm``
and ``out``.

Decode goes through the same entry points as ``transformer_lm``:
``models.transformer.generate`` offline (the streaming carries of the
attention and Mamba layers ride ``rnn_time_step``) and the paged serving
engine (``serving.decode``), whose walker gives each stateful vertex its
state: K/V pools to attention, a convolution tail and an SSM state a lane
to Mamba.
"""

from __future__ import annotations

from typing import Optional

from ..nn.conf.attention import SelfAttentionLayer
from ..nn.conf.builders import NeuralNetConfiguration
from ..nn.conf.graph import ElementWiseVertex
from ..nn.conf.inputs import InputType
from ..nn.conf.layers import EmbeddingSequenceLayer, RMSNorm, RnnOutputLayer
from ..nn.conf.moe import LatentMoELayer
from ..nn.conf.ssm import Mamba2Mixer

PATTERN_KINDS = "ME*"


def nemotron_h_lm(vocab_size: int, *, pattern: str, d_model: int,
                  # attention
                  n_heads: int, n_kv_heads: int,
                  # Mamba-2
                  mamba_heads: int, mamba_head_dim: int, mamba_groups: int,
                  state_size: int, conv_kernel: int = 4,
                  chunk_size: int = 128,
                  # LatentMoE
                  n_experts: int, top_k: int, d_latent: int, d_expert: int,
                  d_shared: int, routed_scale: float = 1.0,
                  experts_held: Optional[int] = None, expert_offset: int = 0,
                  norm_eps: float = 1e-5, seed: int = 42,
                  dtype: str = "float32",
                  max_cache_t: Optional[int] = None):
    """The graph of ``pattern`` (a string over ``M``, ``E``, ``*``), ids in
    (``[b, t]`` int32), a softmax over ``vocab_size`` out. ``max_cache_t``
    arms the attention layers' streaming K/V cache, as in
    ``transformer_lm``; ``experts_held``/``expert_offset`` make every
    expert layer hold that share of its ``n_experts`` (one chip of an
    expert-parallel deployment). A serving model: ``dtype`` may be
    ``stored_bf16``; the updater is plain SGD at rate 0."""
    bad = sorted(set(pattern) - set(PATTERN_KINDS))
    if bad or not pattern:
        raise ValueError(f"pattern {pattern!r}: layers are of kinds "
                         f"{PATTERN_KINDS!r}, got {bad}")
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater("sgd").learning_rate(0.0).dtype(dtype)
          .graph_builder().add_inputs("in"))
    gb.add_layer("embed", EmbeddingSequenceLayer(
        n_in=vocab_size, n_out=d_model, activation="identity"), "in")
    prev = "embed"
    for i, kind in enumerate(pattern):
        if kind == "M":
            mixer = Mamba2Mixer(
                n_in=d_model, n_out=d_model, n_heads=mamba_heads,
                head_dim=mamba_head_dim, n_groups=mamba_groups,
                state_size=state_size, conv_kernel=conv_kernel,
                chunk_size=chunk_size, norm_eps=norm_eps,
                activation="identity")
        elif kind == "*":
            mixer = SelfAttentionLayer(
                n_in=d_model, n_out=d_model, n_heads=n_heads,
                n_kv_heads=n_kv_heads, has_bias=False, causal=True,
                max_cache_t=max_cache_t, activation="identity")
        else:
            mixer = LatentMoELayer(
                n_in=d_model, n_out=d_model, d_latent=d_latent,
                d_hidden=d_expert, d_shared=d_shared, n_experts=n_experts,
                experts_held=experts_held, expert_offset=expert_offset,
                top_k=top_k, routed_scale=routed_scale,
                activation="identity")
        gb.add_layer(f"l{i}_norm", RMSNorm(eps=norm_eps), prev)
        gb.add_layer(f"l{i}_mix", mixer, f"l{i}_norm")
        gb.add_vertex(f"l{i}_res", ElementWiseVertex(op="add"), prev,
                      f"l{i}_mix")
        prev = f"l{i}_res"
    gb.add_layer("final_norm", RMSNorm(eps=norm_eps), prev)
    gb.add_layer("out", RnnOutputLayer(
        n_in=d_model, n_out=vocab_size, has_bias=False,
        activation="softmax", loss="sparse_mcxent"), "final_norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(1))
    return gb.build()
