"""Multi-head self-attention as a config-DSL layer.

The reference has NO attention layer (LSTM era — SURVEY §2.9); this is the
long-context north-star extension surfaced in the same builder DSL as every
other layer, so sequence models can mix attention with the reference layer
set. Works on recurrent activations [b, t, f]; honours sequence masks the
same way the recurrent layers do (masked keys are not attended, masked
steps output 0).

The single-device path uses the fused ``ops.attention.dot_product_attention``;
inside an ``ops.attention.sequence_sharding`` context (entered by
``parallel.sequence.SequenceParallelGraphTrainer`` around its step trace)
the same math runs as ring attention over the sequence-sharded mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ... import dtypes as _dtypes
from .inputs import InputType
from .layers import Layer, register_layer
from ..weights import init_weights


@register_layer("self_attention")
@dataclasses.dataclass
class SelfAttentionLayer(Layer):
    """Causal/bidirectional multi-head self-attention with output projection.

    Params: fused qkv projection ``Wqkv`` [n_in, 3·h·d], output projection
    ``Wo`` [h·d, n_out], bias ``b`` [n_out]. A head is ``d = head_size``
    wide; without ``head_size`` it is ``n_in / n_heads``, and ``n_in``
    must divide by ``n_heads``.

    ``n_kv_heads`` < ``n_heads`` is grouped-query attention: query head
    ``i`` reads K/V head ``i // (n_heads // n_kv_heads)``; ``Wqkv`` is then
    [n_in, (n_heads + 2·n_kv_heads)·d], columns ``[q | k | v]``, and every
    cache (dense streaming, paged pools) holds ``n_kv_heads·d`` a token.
    ``has_bias=False`` leaves ``b`` out. ``output_gate=True`` adds
    ``Wgate`` [n_in, h·d]: the heads' output, column by column, is
    multiplied by ``sigmoid(u @ Wgate)`` of the layer's input ``u`` before
    ``Wo`` (the gated attention of arXiv:2505.06708; scope ``attn.gate``).
    """

    n_in: Optional[int] = None
    n_out: Optional[int] = None       # defaults to n_in
    n_heads: int = 4
    n_kv_heads: Optional[int] = None  # None = n_heads (multi-head)
    head_size: Optional[int] = None   # None = n_in // n_heads
    output_gate: bool = False
    has_bias: bool = True
    causal: bool = True
    # streaming decode: K/V cache length for rnn_time_step. None = no
    # cache — rnn_time_step then attends WITHIN each fed chunk only (no
    # history), which is almost never what you want for attention; set
    # max_cache_t for true incremental decode. Feeding more than
    # max_cache_t TOTAL steps slides the window: the OLDEST cached
    # positions are evicted (positions stay global, so the causal masks
    # remain correct) and the runtimes emit a RuntimeWarning at the first
    # overflow (util.netutil.note_streamed_steps) — reset with
    # rnn_clear_previous_state() between sequences. Causal layers only.
    max_cache_t: Optional[int] = None
    # what overflowing max_cache_t means: "evict" = sliding-window
    # attention over the most recent max_cache_t positions (the default,
    # and what the paged serving arena does page-at-a-time); "strict" =
    # the runtimes raise util.netutil.StreamingCacheOverflow host-side
    # BEFORE the overflowing dispatch (for callers whose correctness
    # depends on full history)
    cache_overflow: str = "evict"
    # the dispatch arrays apply_paged takes after its pools (serving/decode)
    paged_inputs = ("tables", "write_slots", "rel_pos")

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out or self.n_in,
                                   input_type.timesteps)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in
        if self.head_size is None and self.n_in % self.n_heads:
            raise ValueError(f"n_in={self.n_in} not divisible by "
                             f"n_heads={self.n_heads}")
        if self.n_heads % self.kv_heads:
            raise ValueError(f"n_heads={self.n_heads} not divisible by "
                             f"n_kv_heads={self.n_kv_heads}")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.head_size or self.n_in // self.n_heads

    @property
    def q_width(self) -> int:
        """Features of a token's queries (and of the heads' output)."""
        return self.n_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        """Features a token's K (or V) takes in a cache."""
        return self.kv_heads * self.head_dim

    def _split_qkv(self, qkv):
        """``[b, t, (h + 2·kv)·d]`` → q ``[b, t, h, d]``, k and v
        ``[b, t, kv, d]``. Multi-head keeps the reshape it always had (the
        lowered text of the served OPT block does not change)."""
        b, t = qkv.shape[:2]
        h, kv, d = self.n_heads, self.kv_heads, self.head_dim
        if kv == h:
            qkv = qkv.reshape(b, t, 3, h, d)
            return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q, k, v = jnp.split(qkv, [h * d, (h + kv) * d], axis=-1)
        return (q.reshape(b, t, h, d), k.reshape(b, t, kv, d),
                v.reshape(b, t, kv, d))

    def _repeat_kv(self, x):
        """K or V ``[b, t, kv, d]`` with each head repeated for the query
        heads that read it (the dense paths; the paged read groups the
        queries instead and never copies K/V)."""
        g = self.n_heads // self.kv_heads
        return x if g == 1 else jnp.repeat(x, g, axis=2)

    def preprocessor_for(self, input_type: InputType):
        # same adapters the recurrent layers insert (BaseRecurrentLayer)
        from .preprocessors import (CnnToRnnPreProcessor,
                                    FeedForwardToRnnPreProcessor)
        if input_type.kind == "feedforward":
            return FeedForwardToRnnPreProcessor()
        if input_type.kind == "convolutional":
            return CnnToRnnPreProcessor(height=input_type.height,
                                        width=input_type.width,
                                        channels=input_type.channels)
        return None

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        shapes = {"Wqkv": (self.n_in, self.q_width + 2 * self.kv_width),
                  "Wo": (self.q_width, self.n_out)}
        if self.output_gate:
            shapes["Wgate"] = (self.n_in, self.q_width)
        if self.has_bias:
            shapes["b"] = (self.n_out,)
        return shapes

    def regularized_params(self):
        return ("Wqkv", "Wo")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        k1, k2 = jax.random.split(key)
        wqkv = init_weights(k1, (self.n_in, self.q_width + 2 * self.kv_width),
                            self.weight_init or "XAVIER",
                            fan_in=self.n_in, fan_out=self.q_width,
                            distribution=self.dist, dtype=dt)
        wo = init_weights(k2, (self.q_width, self.n_out),
                          self.weight_init or "XAVIER",
                          fan_in=self.q_width, fan_out=self.n_out,
                          distribution=self.dist, dtype=dt)
        params = {"Wqkv": wqkv, "Wo": wo}
        if self.output_gate:
            params["Wgate"] = init_weights(
                jax.random.fold_in(key, 2), (self.n_in, self.q_width),
                self.weight_init or "XAVIER", fan_in=self.n_in,
                fan_out=self.q_width, distribution=self.dist, dtype=dt)
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,),
                                   float(self.bias_init or 0.0), dt)
        return params

    def _project_out(self, params, att, xc):
        """``att [b, t, h·d]`` through the output gate (of the layer's
        input ``xc``), ``Wo`` (+ ``b``) and the activation."""
        if self.output_gate:
            with jax.named_scope("attn.gate"):
                att = att * jax.nn.sigmoid(
                    xc @ params["Wgate"].astype(xc.dtype))
        out = att @ params["Wo"].astype(att.dtype)
        if self.has_bias:
            out = out + params["b"].astype(att.dtype)
        return self._act(self.activation or "identity")(out)

    def _zero_state(self, batch, policy):
        """Streaming K/V cache (only when ``max_cache_t`` is set): rides
        the same h/c carry machinery as the recurrent layers —
        ``h``/``c`` are the [b, max_t+1, n_in] K/V caches whose LAST row
        smuggles the write position (the carry contract is h/c-shaped,
        so the counter lives in-band)."""
        if self.max_cache_t is None:
            raise ValueError(
                "SelfAttentionLayer streaming needs max_cache_t set")
        if self.cache_overflow not in ("evict", "strict"):
            raise ValueError(
                f"cache_overflow={self.cache_overflow!r} — expected "
                "'evict' or 'strict'")
        if not self.causal:
            raise ValueError(
                "SelfAttentionLayer streaming decode requires causal=True "
                "(incremental decode of bidirectional attention is "
                "ill-defined — later tokens would change earlier outputs)")
        # at least f32: the in-band position counter must count exactly
        # (bf16 rounds integers past 256), and cached K/V precision
        # benefits too
        dt = jnp.promote_types(policy.compute_dtype, jnp.float32)
        shape = (batch, self.max_cache_t + 1, self.kv_width)
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def _apply_streaming(self, params, xc, state, policy):
        """Incremental decode: append this chunk's K/V to the cache and
        attend the new queries over everything cached so far (causal
        across calls). O(t_new · cached) instead of O(T²) per token.

        Overflow is sliding-window EVICTION: once the fed total exceeds
        ``max_cache_t`` the oldest cached positions are rolled out, so
        the cache always holds the most recent ``max_cache_t`` tokens.
        Positions stay GLOBAL — the in-band counter keeps counting fed
        steps and the causal mask is computed in view-relative terms
        (slot j holds global position ``base + j``).

        Eviction is CHUNK-granular: the whole chunk's worth of old
        positions is evicted before any of the chunk's queries attend,
        so in an overflowing multi-step chunk query i sees
        ``max_cache_t - (t_new - 1 - i)`` back-positions, not the full
        window (the chunk's LAST query always sees exactly
        ``(p - max_cache_t, p]``). Token-by-token decode (t_new=1 — the
        decode loops' shape) therefore gets the exact per-token sliding
        window; callers that need it for long prompts feed the
        over-window tail in single steps (``models.transformer.
        generate`` does). The paged serving arena makes the matching
        choice at page granularity. Below the window this is a no-op
        (shift 0) and the math is bit-identical to the pre-eviction
        path."""
        b, t_new, _ = xc.shape
        h, d = self.n_heads, self.head_dim
        max_t = self.max_cache_t
        if t_new > max_t:   # shapes are static: fail at trace, not silently
            raise ValueError(
                f"streaming chunk of {t_new} steps exceeds "
                f"max_cache_t={max_t}; raise max_cache_t or feed smaller "
                "chunks")
        wqkv = params["Wqkv"].astype(xc.dtype)
        q, k_new, v_new = self._split_qkv(xc @ wqkv)
        kw = self.kv_width
        k_cache, v_cache = state["h"], state["c"]
        pos = k_cache[0, -1, 0].astype(jnp.int32)
        # cache slot j holds global position base + j; this call may
        # advance base (evict) so the t_new new tokens fit at the end
        old_base = jnp.maximum(pos - max_t, 0)
        new_base = jnp.maximum(pos + t_new - max_t, 0)
        shift = new_base - old_base            # positions evicted now
        write_pos = pos - new_base             # == min(pos, max_t - t_new)
        # the roll is a whole-window gather — only pay it on the calls
        # that actually evict (shift stays 0 until the window fills)
        body_k, body_v = jax.lax.cond(
            shift > 0,
            lambda kv: (jnp.roll(kv[0], -shift, axis=1),
                        jnp.roll(kv[1], -shift, axis=1)),
            lambda kv: kv,
            (k_cache[:, :max_t], v_cache[:, :max_t]))
        k_flat = k_new.reshape(b, t_new, kw).astype(k_cache.dtype)
        v_flat = v_new.reshape(b, t_new, kw).astype(v_cache.dtype)
        zero = jnp.zeros((), pos.dtype)
        body_k = jax.lax.dynamic_update_slice(body_k, k_flat,
                                              (zero, write_pos, zero))
        body_v = jax.lax.dynamic_update_slice(body_v, v_flat,
                                              (zero, write_pos, zero))
        kh = self._repeat_kv(body_k.reshape(b, max_t, self.kv_heads, d))
        vh = self._repeat_kv(body_v.reshape(b, max_t, self.kv_heads, d))
        scale = 1.0 / jnp.sqrt(d).astype(xc.dtype)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kh) * scale
        # new query i sits at global position pos+i = view slot
        # write_pos+i: attend view slots <= write_pos+i (evicted
        # positions are simply absent from the view)
        key_idx = jnp.arange(max_t)
        q_idx = write_pos + jnp.arange(t_new)
        allow = key_idx[None, :] <= q_idx[:, None]          # [t_new, max_t]
        logits = jnp.where(allow[None, None], logits.astype(jnp.float32),
                           -jnp.inf)
        m = jnp.max(logits, axis=-1, keepdims=True)
        m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
        p = jnp.where(jnp.isneginf(logits), 0.0, jnp.exp(logits - m_safe))
        weights = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True),
                                  1e-30)
        att = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(xc.dtype), vh)
        out = self._project_out(params, att.reshape(b, t_new, h * d), xc)
        new_pos = (pos + t_new).astype(k_cache.dtype)
        k_cache = jnp.concatenate(
            [body_k, k_cache[:, max_t:].at[:, 0, 0].set(new_pos)], axis=1)
        v_cache = jnp.concatenate(
            [body_v, v_cache[:, max_t:].at[:, 0, 0].set(new_pos)], axis=1)
        return out, {"h": k_cache, "c": v_cache}

    def paged_entry(self, lanes: int):
        """The arena's entry (``serving/kv_cache.PagedEntry``): a K and a V
        pool of ``kv_width`` a token."""
        from ...serving.kv_cache import PagedEntry
        return PagedEntry((self.kv_width,) * 2, self.kv_heads)

    @property
    def paged_read(self) -> Tuple[int, int, int]:
        """``(group, kv_heads, d_v)``: query heads a K/V head, K/V heads and
        the value's width, which sets the paged read's trip."""
        return self.n_heads // self.kv_heads, self.kv_heads, self.head_dim

    def apply_paged(self, params, x, k_pool, v_pool, page_table,
                    write_slots, rel_pos, *, policy=None):
        """Paged-arena streaming decode (the serving continuous-batching
        path): K/V live in shared ``[num_pages, page_size, h*d]`` block
        pools instead of a per-sequence dense cache (a token's heads in
        one row: the layout a program's entry parameter keeps through
        the scatter and the gather, so no pool is copied; nothing here
        may reshape a pool, ``ops.paged_attention``); each lane's page
        table names its window, which ``ops.paged_attention.
        paged_read_attention`` reads a chunk of pages at a time, as far
        as the furthest live position of the dispatch, under a running
        softmax. Against :meth:`_apply_streaming`: the same keys in the
        same dtypes, the float32 sums formed chunk by chunk, so logits
        agree to rounding and ``tests/test_decode.py`` pins the GREEDY
        TOKENS through the arena equal to the dense full-cache path for
        sequences within the window. Sliding-window
        overflow is PAGE eviction, done host-side by the serving engine
        (page table shifts, ``rel_pos`` stays put); positions stay
        global throughout, but past the window the paged and dense
        paths legitimately differ by eviction granularity (a page vs a
        token at a time).

        x: ``[S, t_new, f]`` raw input activations; write_slots:
        ``[S, t_new]`` view-relative write slots (-1 = padded, dropped);
        rel_pos: ``[S]`` view-relative position of the first new query.
        Returns ``(out, k_pool, v_pool)``.
        """
        from ...ops.paged_attention import paged_read_attention, paged_write
        policy = policy or _dtypes.default_policy()
        xc, wqkv = policy.cast_to_compute(x, params["Wqkv"])
        b, t_new, _ = xc.shape
        h, d = self.n_heads, self.head_dim
        with jax.named_scope("attn.qkv"):
            q, k_new, v_new = self._split_qkv(xc @ wqkv)
        k_pool = paged_write(k_pool, k_new, page_table, write_slots)
        v_pool = paged_write(v_pool, v_new, page_table, write_slots)
        scale = 1.0 / jnp.sqrt(d).astype(xc.dtype)
        group = h // self.kv_heads
        if group == 1:
            att = paged_read_attention(q, k_pool, v_pool, page_table,
                                       rel_pos, scale)
        else:
            # the query heads of one K/V head become further queries of
            # it: [S, t, kv, g, d] -> [S, t·g, kv, d], so the read's
            # products have g rows a key and K/V are gathered once
            kv = self.kv_heads
            qg = q.reshape(b, t_new, kv, group, d).swapaxes(2, 3)
            att = paged_read_attention(
                qg.reshape(b, t_new * group, kv, d), k_pool, v_pool,
                page_table, rel_pos, scale, group=group)
            att = att.reshape(b, t_new, group, kv, d).swapaxes(2, 3)
        with jax.named_scope("attn.out"):
            out = self._project_out(params, att.reshape(b, t_new, h * d), xc)
        return out, k_pool, v_pool

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        from ...ops.attention import (active_sequence_sharding,
                                      dot_product_attention,
                                      make_ring_attention)
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        xc, wqkv = policy.cast_to_compute(x, params["Wqkv"])
        if (not train and mask is None and self.max_cache_t is not None
                and state is not None and "h" in state):
            # streaming decode with the carried K/V cache (rnn_time_step)
            return self._apply_streaming(params, xc, state, policy)
        b, t, _ = xc.shape
        with jax.named_scope("attn.qkv"):
            q, k, v = self._split_qkv(xc @ wqkv)
            k, v = self._repeat_kv(k), self._repeat_kv(v)
        seq_ctx = active_sequence_sharding()
        if seq_ctx is not None and seq_ctx[1] is not None:
            # sequence-parallel route: the time axis is sharded over the
            # mesh — the one op that mixes timesteps runs as ring attention
            # (K/V shards rotate over ppermute; see parallel/sequence.py).
            # Key masks ride the ring too: each mask shard rotates with
            # its K/V shard.
            mesh, seq_axis, batch_axis = seq_ctx
            if mask is None:
                ring = make_ring_attention(mesh, seq_axis,
                                           causal=self.causal,
                                           batch_axis=batch_axis)
                att = ring(q, k, v)
            else:
                ring = make_ring_attention(mesh, seq_axis,
                                           causal=self.causal,
                                           batch_axis=batch_axis,
                                           with_mask=True)
                att = ring(q, k, v, mask)
        else:
            att = dot_product_attention(q, k, v, causal=self.causal,
                                        mask=mask)
        with jax.named_scope("attn.out"):
            out = self._project_out(params, att.reshape(b, t, self.q_width),
                                    xc)
            if mask is not None:
                out = out * mask[:, :, None].astype(out.dtype)
        return out, state
