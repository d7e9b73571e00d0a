"""Multi-head latent attention (MLA) with a rotary term, as a config-DSL
layer: the attention of the DeepSeek-V2 family of decoders, which
``pangu_ultra_moe`` shares.

A token's keys and values are not cached per head. They are functions of
one low-rank latent ``c`` (``kv_rank`` wide, normalised) and of one rotary
key row shared by every head, so a cache holds ``[c | k_rope]``,
``kv_rank + rope_dim`` numbers a token and layer, whatever the number of
heads. With ``H`` heads, input ``u`` at absolute position ``t``:

    cq              = N(u @ W_qa; q_norm_g)                 q_rank
    [q_nope | q_r]  = cq @ W_qb                  per head: nope_dim | rope_dim
    [ckv | k_r]     = u @ W_kva                             kv_rank | rope_dim
    c               = N(ckv; kv_norm_g)
    k_rope          = R_t(k_r)                              one row, all heads
    [k_nope | v]    = c @ W_kvb                  per head: nope_dim | v_dim
    score_h(t, s)   = (q_nope_h . k_nope_h(s) + R_t(q_r_h) . k_rope(s))
                      / sqrt(nope_dim + rope_dim)
    out             = concat_h(softmax_s(score_h) v_h) @ W_o

``N`` is RMSNorm, ``R_t`` the rotary map of ``nn/conf/rotary``. Softmax,
both latents' norms and the rotary angles are float32 under every policy.

Two forms of the same numbers:

- **textbook** (:meth:`MLAttentionLayer.apply`, a whole sequence): keys and
  values expanded per head from ``c``.
- **absorbed** (the dense streaming carry and :meth:`apply_paged`): with
  ``W_kvb`` split per head into ``W_uk_h`` and ``W_uv_h``, ``q_lat_h =
  q_nope_h @ W_uk_h^T`` and ``score_h = [q_lat_h | R(q_r_h)] . [c | k_rope]``,
  ``o_h = (sum_s p c(s)) @ W_uv_h``: ONE key/value head of width ``kv_rank +
  rope_dim`` read by ``H`` query heads, the value the row's first ``kv_rank``
  columns. Nothing is expanded, so a decode step reads 576 numbers a cached
  token where per-head K/V would read ``H x (192 + 128)``.

Scopes: ``mla.q_down``, ``mla.q_up``, ``mla.kv_down``, ``mla.rope``,
``mla.absorb_q``, ``mla.absorb_v``, ``attn.out``, and the paged read's own
(``attn.paged_write``, ``attn.paged_gather``, ``attn.paged_softmax``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import dtypes as _dtypes
from .inputs import InputType
from .layers import Layer, register_layer, rms_normalize
from .rotary import rotary
from ..weights import init_weights


def _softmax_rows(logits, allow):
    """Masked softmax over the last axis in float32, with the conventions
    of the other decode paths for a fully masked row."""
    logits = jnp.where(allow, logits.astype(jnp.float32), -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(jnp.isneginf(logits), 0.0, jnp.exp(logits - m))
    return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


@register_layer("mla_attention")
@dataclasses.dataclass
class MLAttentionLayer(Layer):
    """Causal multi-head latent attention: [b, t, n_in] -> [b, t, n_out]
    (module docstring). Params: ``W_qa [n_in, q_rank]``, ``q_norm_g``,
    ``W_qb [q_rank, H·(nope+rope)]``, ``W_kva [n_in, kv_rank+rope]``,
    ``kv_norm_g``, ``W_kvb [kv_rank, H·(nope+v)]``, ``W_o [H·v, n_out]``; no
    bias. ``max_cache_t`` arms the dense streaming carry as it does
    ``SelfAttentionLayer``'s; in the paged arena the layer owns ONE pool of
    ``row_width`` columns."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None       # defaults to n_in
    n_heads: int = 4
    q_rank: int = 48
    kv_rank: int = 32
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    causal: bool = True               # the only kind there is
    max_cache_t: Optional[int] = None
    cache_overflow: str = "evict"
    # the dispatch arrays apply_paged takes after its pool (serving/decode)
    paged_inputs = ("tables", "write_slots", "rel_pos", "positions")

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out or self.n_in,
                                   input_type.timesteps)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in
        if self.rope_dim % 2:
            raise ValueError(f"rope_dim={self.rope_dim} must be even")
        if not self.causal:
            raise ValueError("MLAttentionLayer is causal")

    @property
    def row_width(self) -> int:
        """Numbers a token takes in a cache: ``[c | k_rope]``."""
        return self.kv_rank + self.rope_dim

    @property
    def pool_width(self) -> int:
        """Columns of a row of the paged pool: ``row_width`` rounded up to a
        whole number of 128-lane tiles, the rest zeros. Only for such a row
        does the TPU compiler keep a donated pool in the layout it is
        stored in; at 576 columns it relaid every pool at a program's entry
        and before its result (``ops/paged_attention``, "Layout
        conventions")."""
        return -(-self.row_width // 128) * 128

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        h = self.n_heads
        return {"W_qa": (self.n_in, self.q_rank),
                "q_norm_g": (self.q_rank,),
                "W_qb": (self.q_rank, h * self.qk_dim),
                "W_kva": (self.n_in, self.row_width),
                "kv_norm_g": (self.kv_rank,),
                "W_kvb": (self.kv_rank, h * (self.nope_dim + self.v_dim)),
                "W_o": (h * self.v_dim, self.n_out)}

    def regularized_params(self):
        return ("W_qa", "W_qb", "W_kva", "W_kvb", "W_o")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        out = {}
        for n, (name, shape) in enumerate(sorted(self.param_shapes().items())):
            if len(shape) == 1:
                out[name] = jnp.ones(shape, dt)
                continue
            out[name] = init_weights(
                jax.random.fold_in(key, n), shape,
                self.weight_init or "XAVIER", fan_in=shape[0],
                fan_out=shape[1], distribution=self.dist, dtype=dt)
        return out

    # ---- the projections ---------------------------------------------

    def _queries(self, params, xc, positions):
        """``(q_nope [b, t, H, nope], q_rope [b, t, H, rope])`` in the
        compute dtype, the rotary part turned at ``positions [b, t]``."""
        b, t, _ = xc.shape
        with jax.named_scope("mla.q_down"):
            cq = rms_normalize(xc @ params["W_qa"].astype(xc.dtype),
                               params["q_norm_g"], self.norm_eps)
        with jax.named_scope("mla.q_up"):
            q = (cq @ params["W_qb"].astype(xc.dtype)).reshape(
                b, t, self.n_heads, self.qk_dim)
        q_nope, q_r = q[..., :self.nope_dim], q[..., self.nope_dim:]
        with jax.named_scope("mla.rope"):
            q_rope = rotary(q_r, positions, self.rope_theta).astype(xc.dtype)
        return q_nope, q_rope

    def _latent_rows(self, params, xc, positions):
        """``[c | k_rope]`` of each position, ``[b, t, row_width]``
        float32: what a cache holds."""
        with jax.named_scope("mla.kv_down"):
            kv = (xc @ params["W_kva"].astype(xc.dtype)).astype(jnp.float32)
            c = rms_normalize(kv[..., :self.kv_rank], params["kv_norm_g"],
                              self.norm_eps)
        with jax.named_scope("mla.rope"):
            k_rope = rotary(kv[..., self.kv_rank:], positions,
                            self.rope_theta)
        return jnp.concatenate([c, k_rope], axis=-1)

    def _w_kvb(self, params, dtype):
        """``(W_uk [kv_rank, H, nope], W_uv [kv_rank, H, v])``."""
        w = params["W_kvb"].astype(dtype).reshape(
            self.kv_rank, self.n_heads, self.nope_dim + self.v_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def _absorbed_queries(self, params, xc, positions):
        """``[q_lat | q_rope]`` ``[b, t, H, row_width]``: a query as the
        latent rows see it."""
        q_nope, q_rope = self._queries(params, xc, positions)
        with jax.named_scope("mla.absorb_q"):
            w_uk, _ = self._w_kvb(params, xc.dtype)
            q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w_uk)
        return jnp.concatenate([q_lat, q_rope], axis=-1)

    def _project_out(self, params, o_lat):
        """``o_lat [b, t, H, kv_rank]`` (the softmax-weighted sum of the
        latents) through ``W_uv`` and ``W_o``."""
        with jax.named_scope("mla.absorb_v"):
            _, w_uv = self._w_kvb(params, o_lat.dtype)
            o = jnp.einsum("bthc,chv->bthv", o_lat, w_uv)
        return self._heads_out(params, o)

    def _heads_out(self, params, o):
        """``o [b, t, H, v]``, the heads' outputs, through ``W_o``."""
        with jax.named_scope("attn.out"):
            out = (o.reshape(o.shape[:2] + (self.n_heads * self.v_dim,))
                   @ params["W_o"].astype(o.dtype))
            return self._act(self.activation or "identity")(out)

    @property
    def _scale(self) -> float:
        return 1.0 / float(np.sqrt(self.qk_dim))

    # ---- a whole sequence: the textbook form ---------------------------

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        xc = policy.cast_to_compute(x)
        if (not train and mask is None and self.max_cache_t is not None
                and state is not None and "h" in state):
            return self._apply_streaming(params, xc, state, policy)
        b, t, _ = xc.shape
        pos = jnp.broadcast_to(jnp.arange(t), (b, t))
        q_nope, q_rope = self._queries(params, xc, pos)
        rows = self._latent_rows(params, xc, pos)
        c = rows[..., :self.kv_rank].astype(xc.dtype)
        k_rope = rows[..., self.kv_rank:].astype(xc.dtype)
        w_uk, w_uv = self._w_kvb(params, xc.dtype)
        k_nope = jnp.einsum("bsc,chn->bshn", c, w_uk)
        v = jnp.einsum("bsc,chv->bshv", c, w_uv)
        with jax.named_scope("attn.dense"):
            logits = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope)
                      + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope)
                      ) * jnp.asarray(self._scale, xc.dtype)
            allow = jnp.tril(jnp.ones((t, t), bool))[None, None]
            if mask is not None:
                allow = allow & (mask[:, None, None, :] > 0)
            p = _softmax_rows(logits, allow)
            o = jnp.einsum("bhts,bshv->bthv", p.astype(xc.dtype), v)
        out = self._heads_out(params, o)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, state

    # ---- the dense streaming carry (generate(), the offline oracle) ----

    def _zero_state(self, batch, policy):
        """``h``: the latent cache ``[b, max_cache_t + 1, row_width]`` whose
        LAST row smuggles the write position, as ``SelfAttentionLayer``'s
        does; ``c`` carries nothing (one cache is all there is)."""
        if self.max_cache_t is None:
            raise ValueError("MLAttentionLayer streaming needs max_cache_t")
        dt = jnp.promote_types(policy.compute_dtype, jnp.float32)
        return (jnp.zeros((batch, self.max_cache_t + 1, self.row_width), dt),
                jnp.zeros((batch, 1, 1), dt))

    def _apply_streaming(self, params, xc, state, policy):
        """``SelfAttentionLayer._apply_streaming``'s contract over the
        latent cache, in the absorbed form: append the chunk's rows, attend
        everything cached; overflow evicts the oldest positions a chunk at a
        time (a cached key keeps the rotation of its absolute position, so
        a slid window still scores by distance)."""
        b, t_new, _ = xc.shape
        max_t = self.max_cache_t
        if t_new > max_t:
            raise ValueError(f"streaming chunk of {t_new} steps exceeds "
                             f"max_cache_t={max_t}")
        cache = state["h"]
        pos = cache[0, -1, 0].astype(jnp.int32)
        positions = jnp.broadcast_to(pos + jnp.arange(t_new), (b, t_new))
        q = self._absorbed_queries(params, xc, positions)
        rows = self._latent_rows(params, xc, positions)
        old_base = jnp.maximum(pos - max_t, 0)
        new_base = jnp.maximum(pos + t_new - max_t, 0)
        shift = new_base - old_base
        write_pos = pos - new_base
        body = jax.lax.cond(shift > 0,
                            lambda kv: jnp.roll(kv, -shift, axis=1),
                            lambda kv: kv, cache[:, :max_t])
        zero = jnp.zeros((), pos.dtype)
        body = jax.lax.dynamic_update_slice(body, rows.astype(cache.dtype),
                                            (zero, write_pos, zero))
        with jax.named_scope("attn.dense"):
            logits = jnp.einsum("bthw,bsw->bhts", q,
                                body.astype(q.dtype)) * self._scale
            allow = (jnp.arange(max_t)[None, :]
                     <= (write_pos + jnp.arange(t_new))[:, None])
            p = _softmax_rows(logits, allow[None, None])
            o_lat = jnp.einsum("bhts,bsc->bthc", p.astype(q.dtype),
                               body[..., :self.kv_rank].astype(q.dtype))
        out = self._project_out(params, o_lat)
        new_pos = (pos + t_new).astype(cache.dtype)
        cache = jnp.concatenate(
            [body, cache[:, max_t:].at[:, 0, 0].set(new_pos)], axis=1)
        return out, {"h": cache, "c": state["c"]}

    # ---- the paged arena -------------------------------------------------

    def paged_entry(self, lanes: int):
        """The arena's entry: ONE pool of ``pool_width``, rows of no heads."""
        from ...serving.kv_cache import PagedEntry
        return PagedEntry((self.pool_width,))

    @property
    def paged_read(self) -> Tuple[int, int, int]:
        """Every head on ONE K/V head, the value ``kv_rank`` wide."""
        return self.n_heads, 1, self.kv_rank

    def apply_paged(self, params, x, pool, page_table, write_slots, rel_pos,
                    positions, *, policy=None):
        """Paged decode over the layer's ONE latent pool ``[num_pages,
        page_size, pool_width]`` (``serving/kv_cache.PagedKVArena``): the
        chunk's rows ``[c | k_rope | 0]`` are scattered in by
        ``ops.paged_attention.paged_write``, and the read is
        ``paged_read_attention`` with ``H`` query heads grouped on one
        key/value head of ``pool_width`` (the queries zero where the rows
        are padding) whose value is the row's first ``kv_rank`` columns. ``x [S, t_new, n_in]``; ``write_slots``,
        ``rel_pos`` as for ``SelfAttentionLayer.apply_paged``;
        ``positions [S]``: the ABSOLUTE position of each lane's first new
        token (the rotary angle; view-relative positions index the cache
        and nothing else). Returns ``(out, pool)``."""
        from ...ops.paged_attention import paged_read_attention, paged_write
        policy = policy or _dtypes.default_policy()
        xc = policy.cast_to_compute(x)
        b, t_new, _ = xc.shape
        h = self.n_heads
        pos = positions[:, None] + jnp.arange(t_new, dtype=positions.dtype)
        pad = self.pool_width - self.row_width
        q = jnp.pad(self._absorbed_queries(params, xc, pos),
                    ((0, 0), (0, 0), (0, 0), (0, pad)))
        rows = jnp.pad(self._latent_rows(params, xc, pos),
                       ((0, 0), (0, 0), (0, pad)))
        pool = paged_write(pool, rows[:, :, None, :], page_table,
                           write_slots)
        o_lat = paged_read_attention(
            q.reshape(b, t_new * h, 1, self.pool_width), pool, None,
            page_table, rel_pos, jnp.asarray(self._scale, xc.dtype),
            group=h, v_width=self.kv_rank)
        out = self._project_out(
            params, o_lat.reshape(b, t_new, h, self.kv_rank).astype(xc.dtype))
        return out, pool
