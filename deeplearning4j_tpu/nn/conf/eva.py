"""EVA attention, as a config-DSL layer: the attention of the EvaByte
decoder. Exact attention inside a fixed window block, and one pooled
key/value row for every chunk of positions before it, in ONE softmax.

Head ``h`` (``D`` columns), input ``y`` at absolute positions ``t`` and
``j``, window ``W``, chunk ``C``:

    q_t = R_t(W_q y_t)      k_j = R_j(W_k y_j)      v_j = W_v y_j
    chunk c = positions C c .. C c + C - 1, once all of them exist:
    k~_c = sum_{j in c} softmax_{j in c}(phi_h . k_j / sqrt(D)) k_j
    v~_c = sum_{j in c} softmax_{j in c}(mu_h . k_j / sqrt(D)) v_j
    query t, w(t) = floor(t / W), attends in one softmax (logits / sqrt(D))
      to k_j, v_j for W w(t) <= j <= t    (its own window block, exactly)
      to k~_c, v~_c for c < (W / C) w(t)  (every chunk of earlier blocks)
    out = concat_h(...) @ W_o

``R`` is the rotary map of ``nn/conf/rotary`` over all ``D`` columns of a
head at the ABSOLUTE position; ``phi_h`` and ``mu_h`` are learned vectors
of ``D`` a head. A sequence of ``N`` positions keeps at most ``W + C - 1``
exact rows and ``N / C`` summary rows a layer, where plain K/V keeps ``N``.

Two forms of the same numbers:

- :meth:`EvaAttentionLayer.apply`, a whole sequence: the window-block
  causal mask beside the summaries of earlier blocks, one softmax.
- :meth:`EvaAttentionLayer.apply_paged`, the serving arena
  (``serving/kv_cache``): the lane's table holds its CURRENT window's pages
  (``W / page_size + 1``: a fused block may cross an edge) and then its
  summary pages, ``page_size`` summaries a page, k~ in the K pool and v~ in
  the V pool. The chunk's new K/V rows are written, every chunk the
  dispatch completed is pooled from its page (``C == page_size``: a chunk
  IS a page) into its summary row, and ``ops.paged_attention.
  eva_read_attention`` merges the window rows and the summary rows in one
  running softmax. The serving engine gives a finished window's pages back
  at each edge.

The pooling softmax, its weighted sums and the read's merge run in
float32 under every policy (the pools are float32). Scopes: ``attn.qkv``,
``eva.summarize``, ``eva.read_summary``, ``eva.read_window``, ``attn.out``
and the paged read's own (``attn.paged_write``, ``attn.paged_gather``,
``attn.paged_softmax``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import dtypes as _dtypes
from .inputs import InputType
from .layers import Layer, register_layer
from .mla import _softmax_rows
from .rotary import rotary
from ..weights import init_weights

_HIGHEST = jax.lax.Precision.HIGHEST


@register_layer("eva_attention")
@dataclasses.dataclass
class EvaAttentionLayer(Layer):
    """Causal EVA attention: [b, t, n_in] -> [b, t, n_out] (module
    docstring). Params: ``W_q``, ``W_k``, ``W_v`` ``[n_in, H·D]``, ``W_o``
    ``[H·D, n_out]``, the pooling vectors ``phi`` and ``mu`` ``[H, D]``; no
    bias. ``max_cache_t`` is the longest sequence served (the serving
    engine holds it to its window, as for the other attention layers); the
    layer keeps no dense streaming cache."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None       # defaults to n_in
    n_heads: int = 4
    head_dim: int = 16
    window_size: int = 64
    chunk_size: int = 16
    rope_theta: float = 10000.0
    causal: bool = True               # the only kind there is
    max_cache_t: Optional[int] = None
    # the dispatch arrays apply_paged takes after its pools (serving/decode)
    paged_inputs = ("tables", "write_slots", "rel_pos", "positions")

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out or self.n_in,
                                   input_type.timesteps)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim} must be even")
        if self.window_size % self.chunk_size:
            raise ValueError(f"window_size={self.window_size} is not a "
                             f"whole number of chunks of {self.chunk_size}")
        if not self.causal:
            raise ValueError("EvaAttentionLayer is causal")

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        hd = self.n_heads * self.head_dim
        return {"W_q": (self.n_in, hd), "W_k": (self.n_in, hd),
                "W_v": (self.n_in, hd), "W_o": (hd, self.n_out),
                "phi": (self.n_heads, self.head_dim),
                "mu": (self.n_heads, self.head_dim)}

    def regularized_params(self):
        return ("W_q", "W_k", "W_v", "W_o")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        return {name: init_weights(
            jax.random.fold_in(key, n), shape, self.weight_init or "XAVIER",
            fan_in=shape[0], fan_out=shape[1], distribution=self.dist,
            dtype=policy.param_dtype)
            for n, (name, shape) in enumerate(
                sorted(self.param_shapes().items()))}

    @property
    def _scale(self) -> float:
        return 1.0 / float(np.sqrt(self.head_dim))

    # ---- the parts both forms share -------------------------------------

    def _qkv(self, params, xc, positions):
        """``q [b, t, H, D]`` in the compute dtype, ``k`` float32 (both
        turned at ``positions [b, t]``), ``v`` in the compute dtype."""
        b, t, _ = xc.shape
        shape = (b, t, self.n_heads, self.head_dim)
        with jax.named_scope("attn.qkv"):
            q, k, v = ((xc @ params[w].astype(xc.dtype)).reshape(shape)
                       for w in ("W_q", "W_k", "W_v"))
            return (rotary(q, positions, self.rope_theta).astype(xc.dtype),
                    rotary(k, positions, self.rope_theta), v)

    def summarize(self, params, k, v):
        """The summary rows of whole chunks: ``k, v [..., C, H, D]`` (one
        chunk's rows on the third axis from the end) -> ``(k~, v~) [...,
        H, D]`` float32, the pooling softmax over the chunk's ``C`` rows at
        full float32 precision."""
        with jax.named_scope("eva.summarize"):
            k = k.astype(jnp.float32)

            def pool(vec, rows):
                w = jax.nn.softmax(jnp.einsum(
                    "...chd,hd->...ch", k, vec.astype(jnp.float32),
                    precision=_HIGHEST) * self._scale, axis=-2)
                return jnp.einsum("...ch,...chd->...hd", w,
                                  rows.astype(jnp.float32),
                                  precision=_HIGHEST)
            return pool(params["phi"], k), pool(params["mu"], v)

    def _project_out(self, params, o):
        with jax.named_scope("attn.out"):
            out = (o.reshape(o.shape[:2] + (self.n_heads * self.head_dim,))
                   @ params["W_o"].astype(o.dtype))
            return self._act(self.activation or "identity")(out)

    # ---- a whole sequence --------------------------------------------------

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        xc = policy.cast_to_compute(self._dropout_in(x, train, rng))
        b, t, _ = xc.shape
        c, h, d = self.chunk_size, self.n_heads, self.head_dim
        q, k, v = self._qkv(params, xc, jnp.broadcast_to(jnp.arange(t),
                                                         (b, t)))
        n = t // c
        ks, vs = self.summarize(params, k[:, :n * c].reshape(b, n, c, h, d),
                                v[:, :n * c].reshape(b, n, c, h, d))
        with jax.named_scope("attn.dense"):
            pos = jnp.arange(t)
            start = pos // self.window_size * self.window_size
            allow = jnp.concatenate(
                [(pos[None, :] <= pos[:, None])
                 & (pos[None, :] >= start[:, None]),
                 jnp.arange(n)[None, :] < (start // c)[:, None]], axis=1)
            allow = allow[None, None]
            if mask is not None:
                keys = jnp.concatenate(
                    [mask > 0, jnp.ones((b, n), bool)], axis=1)
                allow = allow & keys[:, None, None, :]
            logits = jnp.concatenate(
                [jnp.einsum("bqhd,bkhd->bhqk", q, k.astype(q.dtype)),
                 jnp.einsum("bqhd,bnhd->bhqn", q, ks.astype(q.dtype))],
                axis=-1) * jnp.asarray(self._scale, q.dtype)
            p = _softmax_rows(logits, allow).astype(q.dtype)
            o = (jnp.einsum("bhqk,bkhd->bqhd", p[..., :t], v)
                 + jnp.einsum("bhqn,bnhd->bqhd", p[..., t:],
                              vs.astype(q.dtype)))
        out = self._project_out(params, o)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, state

    # ---- the paged arena -----------------------------------------------

    def paged_entry(self, lanes: int):
        """The arena's entry: a K and a V pool of ``H·D``, and a lane's table
        of one window and its summary pages."""
        from ...serving.kv_cache import PagedEntry
        return PagedEntry((self.n_heads * self.head_dim,) * 2, self.n_heads,
                          window=(self.window_size, self.chunk_size))

    @property
    def paged_read(self) -> Tuple[int, int, int]:
        """One query head a K/V head."""
        return 1, self.n_heads, self.head_dim

    def apply_paged(self, params, x, k_pool, v_pool, page_table, write_slots,
                    rel_pos, positions, *, policy=None):
        """Paged decode over the layer's K and V pools ``[num_pages,
        page_size, H·D]``, ``page_size == chunk_size``. ``page_table [S,
        P]``: the lane's window pages first (``window_size / page_size +
        1``, the first holding the window's first position), then its
        summary pages; ``write_slots``, ``rel_pos`` view-relative as for
        ``SelfAttentionLayer.apply_paged`` (slot 0 is the window's first
        position); ``positions [S]``: the ABSOLUTE position of each lane's
        first new token (the rotary angle, the window edges, the chunks).
        Returns ``(out, k_pool, v_pool)``."""
        from ...ops import paged_attention as _paged
        policy = policy or _dtypes.default_policy()
        xc = policy.cast_to_compute(x)
        b, t_new, _ = xc.shape
        num_pages, page_size = k_pool.shape[0], k_pool.shape[1]
        c, h, d = self.chunk_size, self.n_heads, self.head_dim
        if page_size != c:
            raise ValueError(f"EVA pages hold one chunk: page_size="
                             f"{page_size}, chunk_size={c}")
        win = self.window_size // page_size + 1
        win_table, sum_table = page_table[:, :win], page_table[:, win:]
        pos = positions[:, None] + jnp.arange(t_new, dtype=positions.dtype)
        q, k, v = self._qkv(params, xc, pos)
        k_pool = _paged.paged_write(k_pool, k, win_table, write_slots)
        v_pool = _paged.paged_write(v_pool, v, win_table, write_slots)
        # every chunk whose last position this dispatch wrote: its page
        # (slot 0 of the window table is the window's first position, a
        # multiple of C) pooled into row ``chunk % C`` of summary page
        # ``chunk // C``
        n = -(-t_new // c)
        chunk = positions[:, None] // c + jnp.arange(n, dtype=positions.dtype)
        last = positions + jnp.sum(write_slots >= 0, axis=1) - 1
        done = (chunk + 1) * c - 1 <= last[:, None]
        base = (positions - rel_pos) // c
        page = jnp.take_along_axis(
            win_table, jnp.clip(chunk - base[:, None], 0, win - 1), axis=1)
        rows = [jnp.take(pool, page, axis=0, mode="fill", fill_value=0)
                .reshape(b, n, c, h, d) for pool in (k_pool, v_pool)]
        ks, vs = self.summarize(params, *rows)
        dest = jnp.take_along_axis(
            sum_table, jnp.clip(chunk // c, 0, sum_table.shape[1] - 1),
            axis=1)
        dest = jnp.where(done, dest, num_pages)       # dropped
        with jax.named_scope("eva.summarize"):
            k_pool = k_pool.at[dest, chunk % c].set(
                ks.reshape(b, n, h * d).astype(k_pool.dtype), mode="drop")
            v_pool = v_pool.at[dest, chunk % c].set(
                vs.reshape(b, n, h * d).astype(v_pool.dtype), mode="drop")
        att = _paged.eva_read_attention(
            q, k_pool, v_pool, page_table, rel_pos, positions,
            jnp.asarray(self._scale, xc.dtype), window=self.window_size,
            chunk_size=c)
        return self._project_out(params, att.astype(xc.dtype)), k_pool, v_pool
