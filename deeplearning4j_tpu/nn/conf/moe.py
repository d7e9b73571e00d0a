"""Mixture-of-experts FFN as a config-DSL layer.

No reference analog (SURVEY §2.9: EP = NO) — the expert-parallelism
north-star surfaced in the same builder DSL as every other layer, so MoE
transformers are ordinary ComputationGraphs (serde, listeners, remat,
SP/PP trainers all apply). The math is ``parallel/expert.py``'s
dense-dispatch formulation (every expert computes every token, top-k
gates zero the rest — static shapes, no scatter, compiler-friendly) with
the time axis preserved, so under a mesh the expert-stacked einsums
partition over ``ep`` (see ``parallel.expert.expert_param_specs`` /
``ExpertParallelGraphTrainer``) and
the time axis can simultaneously shard over ``seq``.

The Shazeer-style load-balancing auxiliary loss is returned through the
layer's state under ``"aux_loss"`` — both network runtimes add any such
entries to the training objective (scaled by ``aux_weight`` here, so the
trainer just sums).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ... import dtypes as _dtypes
from .inputs import InputType
from .layers import Layer, register_layer
from ..weights import init_weights


def top_k_route(select, gates, k: int):
    """The routing choice both expert layers share: the ``k`` experts with
    the largest ``select`` (``lax.top_k``: ties go to the lowest index, so
    exactly ``k`` fire even for uniform scores) and their ``gates``.
    Returns ``(idx [..., k], chosen gates [..., k])``."""
    _, idx = jax.lax.top_k(select, k)
    return idx, jnp.take_along_axis(gates, idx, axis=-1)


@register_layer("moe")
@dataclasses.dataclass
class MoELayer(Layer):
    """Top-k routed mixture-of-experts FFN: [b, t, f] → [b, t, f] (or
    [b, f] → [b, f]).

    Params: ``router`` [n_in, E], expert-stacked ``w1`` [E, n_in,
    d_hidden], ``b1`` [E, d_hidden], ``w2`` [E, d_hidden, n_out], ``b2``
    [E, n_out] — the leading E dim is what expert parallelism shards.
    """

    n_in: Optional[int] = None
    n_out: Optional[int] = None          # defaults to n_in
    d_hidden: int = 256
    n_experts: int = 8
    top_k: int = 2
    aux_weight: float = 0.01
    _trace_scope = "ffn"

    def output_type(self, input_type: InputType) -> InputType:
        n = self.n_out or self.n_in
        if input_type.kind == "recurrent":
            return InputType.recurrent(n, input_type.timesteps)
        return InputType.feed_forward(n)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in
        if self.top_k > self.n_experts:
            raise ValueError(f"top_k={self.top_k} > "
                             f"n_experts={self.n_experts}")

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        e, h = self.n_experts, self.d_hidden
        return {"router": (self.n_in, e),
                "w1": (e, self.n_in, h), "b1": (e, h),
                "w2": (e, h, self.n_out), "b2": (e, self.n_out)}

    def regularized_params(self) -> Tuple[str, ...]:
        return ("w1", "w2")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        e, h = self.n_experts, self.d_hidden
        kr, k1, k2 = jax.random.split(key, 3)
        wi = self.weight_init or "XAVIER"

        def stack(k, shape, fan_in, fan_out):
            ks = jax.random.split(k, e)
            return jnp.stack([
                init_weights(ks[i], shape, wi, fan_in=fan_in,
                             fan_out=fan_out, distribution=self.dist,
                             dtype=dt) for i in range(e)])

        return {
            "router": init_weights(kr, (self.n_in, e), wi,
                                   fan_in=self.n_in, fan_out=e, dtype=dt),
            "w1": stack(k1, (self.n_in, h), self.n_in, h),
            "b1": jnp.zeros((e, h), dt),
            "w2": stack(k2, (h, self.n_out), h, self.n_out),
            "b2": jnp.zeros((e, self.n_out), dt),
        }

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]                       # [b, 1, f]
        xc, router = policy.cast_to_compute(x, params["router"])
        e = self.n_experts
        logits = jnp.einsum("btd,de->bte", xc, router)
        # routing numerics at >= f32 (and f64 under an x64 policy, so the
        # gradient-check suite sees the true derivative)
        gate_dt = jnp.promote_types(logits.dtype, jnp.float32)
        gates = jax.nn.softmax(logits.astype(gate_dt), axis=-1)
        if self.top_k < e:
            # lax.top_k breaks ties deterministically (lowest index), so
            # EXACTLY top_k experts fire even for uniform gates
            idx, _ = top_k_route(gates, gates, self.top_k)  # [b, t, k]
            keep = jax.nn.one_hot(idx, e).sum(axis=2) > 0   # [b, t, E]
            masked = jnp.where(keep, gates, 0.0)
            weights = masked / jnp.maximum(
                masked.sum(-1, keepdims=True), 1e-9)
        else:
            keep = jnp.ones_like(gates, bool)
            weights = gates
        w1 = params["w1"].astype(xc.dtype)
        w2 = params["w2"].astype(xc.dtype)
        # dense dispatch, time axis preserved: [E, b, t, h] hidden
        h = jax.nn.relu(jnp.einsum("btd,edh->ebth", xc, w1)
                        + params["b1"].astype(xc.dtype)[:, None, None, :])
        y_e = (jnp.einsum("ebth,ehd->ebtd", h, w2)
               + params["b2"].astype(xc.dtype)[:, None, None, :])
        y = jnp.einsum("bte,ebtd->btd", weights.astype(xc.dtype), y_e)
        # Shazeer-style load-balancing aux: E * sum_e mean_gate * mean_keep
        if mask is not None:
            m = mask.astype(gate_dt)[:, :, None]
            denom = jnp.maximum(jnp.sum(m), 1.0)
            gate_frac = jnp.sum(gates * m, axis=(0, 1)) / denom
            keep_frac = jnp.sum(keep.astype(gate_dt) * m,
                                axis=(0, 1)) / denom
            y = y * m.astype(y.dtype)
        else:
            gate_frac = jnp.mean(gates, axis=(0, 1))
            keep_frac = jnp.mean(keep.astype(gate_dt), axis=(0, 1))
        aux = e * jnp.sum(gate_frac * keep_frac)
        if squeeze:
            y = y[:, 0, :]
        out_state = dict(state or {})
        out_state["aux_loss"] = self.aux_weight * aux
        return y, out_state


# --------------------------------------------------------------------------
# LatentMoE: experts in a latent width, a share of them held here
# --------------------------------------------------------------------------

# what an expert layer reports beside its output, summed over a dispatch's
# layers and steps (``state["moe_stats"]``, int32): routed (token, expert)
# pairs whose expert is held here, pairs whose expert is absent, rows the
# grouped product computed (padding included), the most loaded held
# expert's pairs, how many (layer, step) observations that is, and the held
# experts that got a pair at all (whose matrices a step has to read)
MOE_STATS = ("held", "absent", "computed", "peak", "steps", "touched")


def tile_rows(pairs: int, n_published: int) -> int:
    """Rows of one tile of the grouped product: the power of two at or
    above the mean number of pairs an expert gets, between 2 and 256. A
    one-token step over many small experts multiplies tiles of 2 rows (an
    expert there sees one or two), a prefill chunk of thousands of tokens
    tiles of up to 256."""
    mean = max(1.0, pairs / float(n_published))
    return int(min(256, max(2, 1 << (math.ceil(mean) - 1).bit_length())))


def sparse_expert_ffn(lat, idx, weights, *stacks, offset: int,
                      n_published: int, token_mask=None):
    """The routed part of an expert layer by sparse dispatch: for each
    token the sum over its chosen experts HELD HERE (``offset <= e <
    offset + E``) of ``w_e · relu(lat @ w1[e])^2 @ w2[e]``; what an absent
    expert would add is left out. ``lat [T, L]``, ``idx [T, k]`` int32
    expert ids over all ``n_published``, ``weights [T, k]`` float32;
    ``stacks``: ``w1 [E, L, F]``, ``w2 [E, F, L]``, or three of a GATED
    expert, ``wg``, ``wu [E, L, F]`` and ``wd [E, F, L]``, whose term is
    ``w_e · (silu(lat @ wg[e]) * (lat @ wu[e])) @ wd[e]``; ``token_mask
    [T]`` (or None) leaves a padded token's pairs out of the work and of
    the counts.

    The (token, expert) pairs are sorted by expert, so each held expert's
    rows lie together, and cut into tiles of :func:`tile_rows` rows, one
    expert a tile; ``ops.grouped_ffn.tile_ffn`` multiplies the tiles the
    routing needs (the sum over held experts of ``ceil(pairs / rows)``)
    and no other: work grows with the pairs routed here, not with tokens
    x experts held, and an expert nobody chose is never read. No pair is
    dropped, whatever the imbalance: an expert with every pair gets
    ``ceil(T·k / rows)`` tiles. Each pair's result returns to its token's
    slot and a token sums its ``k`` slots in their routing order, so a
    token's result does not depend on what shares its batch.

    Returns ``(out [T, L] float32, stats int32[len(MOE_STATS)])``.
    """
    from ...ops.grouped_ffn import tile_ffn
    t, k = idx.shape
    e_held = stacks[0].shape[0]
    m = t * k
    rows = tile_rows(m, n_published)
    local = idx - offset
    held = (local >= 0) & (local < e_held)
    if token_mask is not None:
        live = token_mask[:, None]
        absent_n = jnp.sum(live & jnp.logical_not(held))
        held = held & live
    else:
        absent_n = jnp.sum(jnp.logical_not(held))
    gid = jnp.where(held, local, e_held).reshape(m)      # absent: sorted last
    # a counting sort (stable, and no sort to compile: a sort of a prefill
    # chunk's 90,000 pairs in each of five layers was most of that
    # program's compile time): pair j goes to its expert's start plus the
    # number of earlier pairs of the same expert
    mine = gid[:, None] == jnp.arange(e_held + 1, dtype=gid.dtype)[None, :]
    seen = jnp.cumsum(mine.astype(jnp.int32), axis=0)      # [m, E + 1]
    all_counts = seen[-1]
    all_starts = jnp.cumsum(all_counts) - all_counts
    rank = jnp.take_along_axis(seen, gid[:, None], axis=1)[:, 0] - 1
    order = jnp.zeros(m, jnp.int32).at[jnp.take(all_starts, gid) + rank].set(
        jnp.arange(m, dtype=jnp.int32))
    counts, starts = all_counts[:e_held], all_starts[:e_held]
    tiles_per = (counts + rows - 1) // rows
    tile_end = jnp.cumsum(tiles_per)
    tile_start = tile_end - tiles_per
    n_tiles = tile_end[-1]
    max_tiles = e_held + m // rows                       # the sum's bound
    tile = jnp.arange(max_tiles, dtype=jnp.int32)
    tile_e = jnp.minimum(jnp.searchsorted(tile_end, tile, side="right"),
                         e_held - 1).astype(jnp.int32)
    within = tile - tile_start[tile_e]
    tile_n = jnp.where(tile < n_tiles,
                       jnp.clip(counts[tile_e] - within * rows, 0, rows), 0)
    # tile i holds the sorted pairs starts[e] + within·rows ..: their
    # tokens' rows, zeros past the tile's count
    pos = (starts[tile_e] + within * rows)[:, None] + jnp.arange(rows)
    in_tile = jnp.arange(rows)[None, :] < tile_n[:, None]
    tok = jnp.take(order // k, jnp.clip(pos, 0, m - 1))
    x_tiles = jnp.where(in_tile[:, :, None], jnp.take(lat, tok, axis=0), 0)
    out_tiles = tile_ffn(x_tiles, tile_e, n_tiles.astype(jnp.int32), *stacks)
    # back to each pair's own slot: pair j is the rank-th of its expert's
    # sorted run, so row rank % rows of that expert's tile rank // rows
    g = jnp.minimum(gid, e_held - 1)
    per_pair = out_tiles[jnp.minimum(tile_start[g] + rank // rows,
                                     max_tiles - 1), rank % rows]
    w = jnp.where(held, weights, 0.0).astype(jnp.float32)
    out = jnp.sum(w[:, :, None] * per_pair.reshape(t, k, -1), axis=1)
    held_n = jnp.sum(counts)
    stats = jnp.stack([held_n, absent_n, n_tiles * rows, jnp.max(counts),
                       (held_n + absent_n > 0),
                       jnp.sum(counts > 0)]).astype(jnp.int32)
    return out, stats


def dense_expert_ffn(lat, idx, weights, *stacks, offset: int):
    """The same sum by dense dispatch: every held expert computes every
    token and a mask keeps what was routed. The test oracle of
    :func:`sparse_expert_ffn`; no program calls it."""
    e_held = stacks[0].shape[0]
    up = [jnp.einsum("tl,elf->etf", lat, w.astype(lat.dtype))
          for w in stacks[:-1]]
    h = (jnp.square(jax.nn.relu(up[0])) if len(stacks) == 2
         else jax.nn.silu(up[0]) * up[1])
    y = jnp.einsum("etf,efl->etl", h, stacks[-1].astype(lat.dtype))
    chosen = (idx[None] == (offset + jnp.arange(e_held))[:, None, None])
    w = jnp.sum(jnp.where(chosen, weights[None].astype(jnp.float32), 0.0),
                axis=-1)                                  # [E, T]
    return jnp.einsum("et,etl->tl", w, y.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


@register_layer("latent_moe")
@dataclasses.dataclass
class LatentMoELayer(Layer):
    """Routed experts in a latent width, with a shared expert beside them
    (``nemotron_h``'s LatentMoE): [b, t, f] -> [b, t, f].

        s      = sigmoid(u @ router)          float32, all n_experts wide
        chosen = the top_k of s + e_bias      (the bias chooses only)
        w_e    = s_e / (sum over chosen of s + 1e-20) · routed_scale
        lat    = u @ W_down                   (n_in -> d_latent)
        routed = (sum over chosen e of w_e · relu(lat @ w1[e])^2 @ w2[e]) @ W_up
        shared = relu(u @ ws1)^2 @ ws2        (in the hidden width)
        out    = routed + shared

    **A layer that holds a share.** ``experts_held`` < ``n_experts`` with
    ``expert_offset`` ``o`` is what one chip of an expert-parallel
    deployment holds: the router, ``W_down``, ``W_up`` and the shared expert
    whole, and experts ``o .. o + experts_held - 1`` (``w1``/``w2`` stack
    those alone). It routes over all ``n_experts``, computes its own
    experts' part of the sum and leaves out what the absent ones would
    add; that partial result is the layer's output. Nothing stands in for
    the other chips or their exchange. The routed part runs by sparse
    dispatch (:func:`sparse_expert_ffn`).

    Beside its output the layer reports its routing counts
    (``state["moe_stats"]``, :data:`MOE_STATS`); ``mask`` ([b, t], a
    serving dispatch's valid positions) keeps padding out of the work and
    out of the counts.

    Scopes: ``moe.router``, ``moe.latent_down``, ``moe.experts``,
    ``moe.shared``, ``moe.latent_up``.
    """

    n_in: Optional[int] = None
    n_out: Optional[int] = None          # = n_in
    d_latent: int = 64
    d_hidden: int = 128                  # a routed expert's width
    d_shared: int = 256                  # the shared expert's width
    n_experts: int = 8                   # the router's width (published)
    experts_held: Optional[int] = None   # None = all of them
    expert_offset: int = 0
    top_k: int = 2
    routed_scale: float = 1.0
    wants_token_mask = True              # the paged walker hands it `valid`

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out or self.n_in,
                                   input_type.timesteps)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in
        if self.top_k > self.n_experts:
            raise ValueError(f"top_k={self.top_k} > "
                             f"n_experts={self.n_experts}")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.held <= self.n_experts):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.held}"
                f" lie outside the router's {self.n_experts}")

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        d, l, f = self.n_in, self.d_latent, self.d_hidden
        return {"router": (d, self.n_experts), "e_bias": (self.n_experts,),
                "W_down": (d, l), "w1": (self.held, l, f),
                "w2": (self.held, f, l), "W_up": (l, self.n_out),
                "ws1": (d, self.d_shared), "ws2": (self.d_shared, self.n_out)}

    def regularized_params(self) -> Tuple[str, ...]:
        return ("w1", "w2", "ws1", "ws2")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        wi = self.weight_init or "XAVIER"
        out = {}
        for n, (name, shape) in enumerate(sorted(self.param_shapes().items())):
            if name == "e_bias":
                out[name] = jnp.zeros(shape, dt)
                continue
            # one draw a leaf, the expert stacks included (fans from the
            # last two axes): never a Python loop over experts
            out[name] = init_weights(
                jax.random.fold_in(key, n), shape, wi, fan_in=shape[-2],
                fan_out=shape[-1], distribution=self.dist, dtype=dt)
        return out

    def route(self, params, x):
        """``(idx [..., k] int32, weights [..., k] float32)`` of ``x
        [..., n_in]``: the product, the sigmoid and the weights in
        float32, at full precision on the chip."""
        with jax.named_scope("moe.router"):
            s = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32), params["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            idx, chosen = top_k_route(
                s + params["e_bias"].astype(jnp.float32), s, self.top_k)
            weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                                + 1e-20) * self.routed_scale
        return idx.astype(jnp.int32), weights

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        b, t, d = x.shape
        xc = policy.cast_to_compute(x).reshape(b * t, d)
        idx, weights = self.route(params, xc)
        with jax.named_scope("moe.latent_down"):
            lat = xc @ params["W_down"].astype(xc.dtype)
        with jax.named_scope("moe.experts"):
            routed, stats = sparse_expert_ffn(
                lat, idx, weights, params["w1"], params["w2"],
                offset=self.expert_offset, n_published=self.n_experts,
                token_mask=None if mask is None else mask.reshape(b * t))
        with jax.named_scope("moe.latent_up"):
            out = routed.astype(xc.dtype) @ params["W_up"].astype(xc.dtype)
        with jax.named_scope("moe.shared"):
            h = jnp.square(jax.nn.relu(xc @ params["ws1"].astype(xc.dtype)))
            out = out + h @ params["ws2"].astype(xc.dtype)
        out = self._act(self.activation or "identity")(out)
        out_state = dict(state or {})
        out_state["moe_stats"] = stats
        return out.reshape(b, t, self.n_out), out_state


@register_layer("gated_moe")
@dataclasses.dataclass
class GatedMoELayer(LatentMoELayer):
    """Routed GATED experts in the hidden width with a gated shared expert
    beside them (the expert layer of the DeepSeek-V3 family, which
    ``pangu_ultra_moe`` shares): [b, t, f] -> [b, t, f].

        idx, w = LatentMoELayer.route          the one routing rule
        routed = sum over chosen e of w_e · (silu(u @ wg[e]) * (u @ wu[e])) @ wd[e]
        shared = (silu(u @ sg) * (u @ su)) @ sd
        out    = routed + shared

    No latent projections (``d_latent`` is not used): ``wg``, ``wu`` are
    ``[held, n_in, d_hidden]`` and ``wd`` ``[held, d_hidden, n_out]``. It
    holds a share of its experts, dispatches, counts and masks exactly as
    its parent does: one sparse dispatch (:func:`sparse_expert_ffn`), one
    kernel file (``ops/grouped_ffn``), one set of :data:`MOE_STATS`.

    In the hidden width the dispatch's pair-level arrays (tokens x top_k
    rows of ``n_in``, sized for the worst routing: every pair held) are
    the largest temporaries of a prefill program (1 GB at 32 lanes x 128
    tokens x 8 a token x 7680 in float32).

    Scopes: ``moe.router``, ``moe.experts``, ``moe.shared``.
    """

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        d, f, fs = self.n_in, self.d_hidden, self.d_shared
        return {"router": (d, self.n_experts), "e_bias": (self.n_experts,),
                "wg": (self.held, d, f), "wu": (self.held, d, f),
                "wd": (self.held, f, self.n_out),
                "sg": (d, fs), "su": (d, fs), "sd": (fs, self.n_out)}

    def regularized_params(self) -> Tuple[str, ...]:
        return ("wg", "wu", "wd", "sg", "su", "sd")

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        from .layers import gated_ffn
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        b, t, d = x.shape
        xc = policy.cast_to_compute(x).reshape(b * t, d)
        idx, weights = self.route(params, xc)
        with jax.named_scope("moe.experts"):
            routed, stats = sparse_expert_ffn(
                xc, idx, weights, params["wg"], params["wu"], params["wd"],
                offset=self.expert_offset, n_published=self.n_experts,
                token_mask=None if mask is None else mask.reshape(b * t))
        with jax.named_scope("moe.shared"):
            out = routed.astype(xc.dtype) + gated_ffn(
                xc, params["sg"], params["su"], params["sd"])
        out = self._act(self.activation or "identity")(out)
        out_state = dict(state or {})
        out_state["moe_stats"] = stats
        return out.reshape(b, t, self.n_out), out_state
