"""Kimi delta attention (KDA) as a config-DSL layer: a gated delta rule
with a decay per key channel.

No reference analog. The linear-attention mixer of Kimi Linear (arXiv
2510.26692) and of the ``solar_open2`` hybrid decoder: per head a matrix
of state ``S`` (``d_k x d_v``) that each token first decays channel by
channel, then partly erases along its key and writes its value into, so
that a sequence's memory is a FIXED-SIZE state, whatever its length. With
``H`` heads of ``D`` channels (``d_k = d_v = D``), low-rank gates of rank
``r``, a depthwise causal convolution of width ``K`` and ``u`` the layer's
input, one row a token:

    [q~ | k~ | v~ | b | f_lo | g_lo] = u @ W_in       (3 x H·D, H, r, r)
    q, k, v = silu(causal depthwise conv_K([q~ | k~ | v~]))
    q_h, k_h <- q_h / |q_h|,  k_h / |k_h|   per head;  q_h <- q_h / sqrt(D)
    beta_h  = 2 · sigmoid(b_h)                         in (0, 2)
    g_h     = -exp(A_log_h) · softplus(f_lo @ W_fu + dt_bias)_h   in R^D, <= 0
    S_t,h   = (I - beta_h k_h k_h^T) · Diag(exp g_h) · S_{t-1,h} + beta_h k_h v_h^T
    o_t,h   = S_t,h^T q_h
    y       = concat_h [RMSNorm(o_h; norm_g) * sigmoid(g_lo @ W_gu)_h] @ W_o

Carried from token to token: the last ``K - 1`` rows of the
PRE-convolution ``[q~ | k~ | v~]`` and ``S`` (``[H, D, D]``), both
float32. Three entry points compute the same numbers:

- :meth:`KimiDeltaAttention.apply` over a whole sequence: the chunked form
  in chunks of ``CHUNK`` (64) positions. With ``G`` the running sum of
  ``g`` inside a chunk (the log of the decay since its start, per channel)
  the chunk's
  writes ``U`` solve one unit lower triangular system (the WY/UT form of
  the delta rule), ``(I + StrictLower(A)) U = beta (V - (K exp G) S_0)``
  with ``A_ts = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)``; the outputs are
  ``(Q exp G) S_0 + Lower(B) U`` with ``B_ts = sum_c q_tc k_sc exp(G_tc -
  G_sc)``, and the state after the chunk ``exp(G_last) S_0 + (K exp(G_last
  - G))^T U``. Between chunks the state is carried by ``lax.scan``.
- the dense streaming contract of ``rnn_time_step`` / ``generate()``:
  ``state={"h": tail, "c": S}`` in, the same out (``_zero_state``).
- :meth:`KimiDeltaAttention.apply_paged` for the serving engine, under
  ``Mamba2Mixer.apply_paged``'s contract: a chunk of ``t_new`` positions
  for ``S`` lanes whose states live in per-lane arrays of the engine's
  state arena; positions where ``valid`` is false advance neither state,
  and ``t_new`` = 1 is the recurrence step itself.

**Exact at any decay.** ``A`` and ``B`` need ``exp(G_t - G_s)`` for every
pair ``s <= t`` of a chunk and channel, and no ``[chunk, chunk, D]`` tensor
a head may be formed (at 32 lanes x 64 heads it would take gigabytes). The
usual factorisation ``(K exp G)(K exp -G)^T`` overflows once a chunk's
log-decays sum past -88 (float32's range). Here the chunk is halved
recursively (:func:`_decayed_pairs`): the pairs whose row lies in the
second half of a block and whose column in the first are one product
against the decay at the last position of the first half, so both factors
are decays of sums that are <= 0, and each pair is taken at the one level
where its two positions part. The products of all levels together cost
what one ``chunk x chunk`` product does.

What stays float32 under a bf16 compute policy: the convolution, beta,
the decay, the state, the chunk algebra (the triangular solve included)
and the norm's statistics. The projections run in the compute dtype.

Scopes (metadata only): ``kda.in_proj``, ``kda.conv``, ``kda.gates``,
``kda.chunk`` (the intra-chunk transform), ``kda.scan`` (between chunks),
``kda.step``, ``kda.norm_out`` (the gated norm and the output projection).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ... import dtypes as _dtypes
from ..weights import init_weights
from .inputs import InputType
from .layers import register_layer
from .recurrent import BaseRecurrentLayer
from .ssm import gather_lanes, scatter_lanes

_HIGHEST = jax.lax.Precision.HIGHEST
_L2_EPS = 1e-6
CHUNK = 64          # positions a chunk of the full-sequence form


def _l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + _L2_EPS)


def _beta(logit):
    """The delta rule's step size in (0, 2) (Kimi Linear's allowance for
    negative eigenvalues of ``I - beta k k^T``)."""
    return 2.0 * jax.nn.sigmoid(logit)


def _decayed_pairs(a, b, gam, *, inclusive: bool):
    """``M [..., L, L]`` with ``M_ts = sum_c a_tc b_sc exp(gam_tc -
    gam_sc)`` for ``s < t`` (and ``s = t`` where ``inclusive``), zeros
    above: ``a``, ``b``, ``gam`` are ``[..., L, D]``, ``gam`` non-increasing
    along ``L`` (a running sum of log-decays), ``L`` a power of two.

    Level by level, a block of ``2h`` positions pairs its second half (rows)
    with its first (columns) through the decay at the first half's last
    position ``r``: ``exp(gam_t - gam_r)`` and ``exp(gam_r - gam_s)`` are
    both at most 1, so nothing overflows however steep the decay, and a
    factor that underflows stands for a product smaller still. The blocks
    of the level below fill the two diagonal quarters."""
    lead, (length, width) = a.shape[:-2], a.shape[-2:]
    m = jnp.zeros(lead + (length, 1, 1), jnp.float32)
    half = 1
    while half < length:
        blocks = length // (2 * half)

        def split(x):
            return x.reshape(lead + (blocks, 2, half, width))
        a2, b2, g2 = split(a), split(b), split(gam)
        ref = g2[..., 0, half - 1:, :]                   # [..., blocks, 1, D]
        rows = a2[..., 1, :, :] * jnp.exp(g2[..., 1, :, :] - ref)
        cols = b2[..., 0, :, :] * jnp.exp(ref - g2[..., 0, :, :])
        low = jnp.einsum("...td,...sd->...ts", rows, cols,
                         precision=_HIGHEST)             # [..., blocks, h, h]
        d = m.reshape(lead + (blocks, 2, half, half))
        top = jnp.concatenate([d[..., 0, :, :], jnp.zeros_like(low)], -1)
        bottom = jnp.concatenate([low, d[..., 1, :, :]], -1)
        m = jnp.concatenate([top, bottom], -2)
        half *= 2
    m = m.reshape(lead + (length, length))
    if inclusive:
        m = m + jnp.sum(a * b, axis=-1)[..., None] * jnp.eye(length)
    return m


@register_layer("kimi_delta_attention")
@dataclasses.dataclass
class KimiDeltaAttention(BaseRecurrentLayer):
    """Kimi delta attention: [b, t, n_in] -> [b, t, n_out] (see module
    docstring).

    Params: ``W_in`` [n_in, 3·H·D + H + 2·r], ``conv_w`` [3·H·D, K] (no
    bias), ``A_log`` [H], ``dt_bias`` [H·D], ``W_fu``, ``W_gu`` [r, H·D],
    ``norm_g`` [D] (one gain for every head), ``W_o`` [H·D, n_out].
    """

    n_heads: int = 4              # H
    head_dim: int = 16            # D = d_k = d_v
    gate_rank: int = 16           # r of the decay's and the output's gates
    conv_kernel: int = 4          # K
    norm_eps: float = 1e-5
    # the dispatch arrays apply_paged takes after its state (serving/decode);
    # a row of state a lane, no paged read
    paged_inputs = ("lane_ids", "valid", "fresh")
    paged_read = None

    # ---- shapes ------------------------------------------------------

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return 3 * self.d_inner

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        super().set_n_in(input_type, override)
        if self.n_out is None:
            self.n_out = self.n_in

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        hd, r = self.d_inner, self.gate_rank
        return {"W_in": (self.n_in, 3 * hd + self.n_heads + 2 * r),
                "conv_w": (self.conv_channels, self.conv_kernel),
                "A_log": (self.n_heads,), "dt_bias": (hd,),
                "W_fu": (r, hd), "W_gu": (r, hd),
                "norm_g": (self.head_dim,), "W_o": (hd, self.n_out)}

    def regularized_params(self) -> Tuple[str, ...]:
        return ("W_in", "W_fu", "W_gu", "W_o")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        shapes = self.param_shapes()
        keys = jax.random.split(key, 7)
        wi = self.weight_init or "XAVIER"

        def matrix(k, name):
            return init_weights(k, shapes[name], wi, fan_in=shapes[name][0],
                                fan_out=shapes[name][1],
                                distribution=self.dist, dtype=dt)
        # Mamba's start, as Kimi Linear takes it: the softplus's output
        # log-uniform on [0.001, 0.1] (dt_bias its inverse), A uniform on
        # [1, 16]
        step = jnp.exp(jax.random.uniform(
            keys[4], (self.d_inner,), jnp.float32, math.log(0.001),
            math.log(0.1)))
        bound = 1.0 / math.sqrt(self.conv_kernel)
        return {
            "W_in": matrix(keys[0], "W_in"), "W_fu": matrix(keys[1], "W_fu"),
            "W_gu": matrix(keys[2], "W_gu"), "W_o": matrix(keys[3], "W_o"),
            "conv_w": jax.random.uniform(keys[5], shapes["conv_w"],
                                         jnp.float32, -bound,
                                         bound).astype(dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "A_log": jnp.log(jax.random.uniform(
                keys[6], (self.n_heads,), jnp.float32, 1.0, 16.0)).astype(dt),
            "norm_g": jnp.ones((self.head_dim,), dt),
        }

    # ---- state -------------------------------------------------------

    def state_shapes(self, lanes: int):
        """(convolution tail, delta-rule state) of ``lanes`` sequences."""
        return ((lanes, self.conv_kernel - 1, self.conv_channels),
                (lanes, self.n_heads, self.head_dim, self.head_dim))

    def paged_entry(self, lanes: int):
        """The arena's entry: every lane's tail and delta-rule state."""
        from ...serving.kv_cache import PagedEntry
        return PagedEntry(lane=self.state_shapes(lanes))

    def _zero_state(self, batch, policy):
        """The dense streaming carry: ``h`` is the convolution's tail,
        ``c`` the state; both at least float32."""
        dt = jnp.promote_types(policy.compute_dtype, jnp.float32)
        tail, state = self.state_shapes(batch)
        return jnp.zeros(tail, dt), jnp.zeros(state, dt)

    # ---- the computation ---------------------------------------------

    def _mix(self, params, x, tail, state, valid, policy):
        """``x [b, t, n_in]`` from the states ``tail [b, K-1, 3·H·D]`` and
        ``state [b, H, D, D]``: returns ``(out, tail, state)``. ``valid``
        (``[b, t]`` bool or None) must be a prefix mask in each row: the
        positions after a row's last valid one are padding, whose content
        reaches neither state."""
        b, t, _ = x.shape
        h, d, r, hd = self.n_heads, self.head_dim, self.gate_rank, self.d_inner
        sdt = state.dtype
        with jax.named_scope("kda.in_proj"):
            xc, w_in = policy.cast_to_compute(x, params["W_in"])
            qkv, beta, f_lo, g_lo = jnp.split(
                xc @ w_in, [3 * hd, 3 * hd + h, 3 * hd + h + r], axis=-1)
        with jax.named_scope("kda.conv"):
            qkv = qkv.astype(sdt)
            if valid is not None:
                qkv = jnp.where(valid[:, :, None], qkv, 0.0)
            cat = jnp.concatenate([tail, qkv], axis=1)     # [b, K-1+t, 3HD]
            w = params["conv_w"].astype(sdt)
            conv = jax.nn.silu(sum(w[:, k] * cat[:, k:k + t]
                                   for k in range(self.conv_kernel)))
            # the tail after this call: the K-1 rows before the first
            # padded position
            n_valid = (jnp.full((b,), t) if valid is None
                       else jnp.sum(valid, axis=1))
            rows = n_valid[:, None] + jnp.arange(self.conv_kernel - 1)
            tail = jnp.take_along_axis(cat, rows[:, :, None], axis=1)
            q, k, v = (c.reshape(b, t, h, d)
                       for c in jnp.split(conv, 3, axis=-1))
            q = _l2_normalize(q) * (1.0 / math.sqrt(d))
            k = _l2_normalize(k)
        with jax.named_scope("kda.gates"):
            beta = _beta(beta.astype(sdt))                 # [b, t, H]
            fc, w_fu = policy.cast_to_compute(f_lo, params["W_fu"])
            f = (fc @ w_fu).astype(sdt) + params["dt_bias"].astype(sdt)
            a = jnp.exp(params["A_log"].astype(sdt))       # [H]
            g = -a[:, None] * jax.nn.softplus(f).reshape(b, t, h, d)
            if valid is not None:     # a padded step: decay 1, no write
                beta = jnp.where(valid[:, :, None], beta, 0.0)
                g = jnp.where(valid[:, :, None, None], g, 0.0)
        o, state = self._recur(q, k, v, g, beta, state)
        with jax.named_scope("kda.norm_out"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + self.norm_eps)
            o = o * params["norm_g"].astype(sdt)
            gc, w_gu = policy.cast_to_compute(g_lo, params["W_gu"])
            gate = jax.nn.sigmoid((gc @ w_gu).astype(sdt))
            y = o.reshape(b, t, hd) * gate
            yc, w_o = policy.cast_to_compute(y, params["W_o"])
            out = yc @ w_o
        return out, tail, state

    def _recur(self, q, k, v, g, beta, state):
        """The delta rule over ``t`` positions from ``state [b, H, D, D]``:
        ``q``, ``k``, ``v``, ``g`` ``[b, t, H, D]``, ``beta [b, t, H]``
        (0 at a padded position, where ``g`` is 0 too). Returns ``(o [b, t,
        H, D], state)``. One position is the recurrence step; more are cut
        into chunks of ``CHUNK`` (or of the power of two at or above
        ``t``, if smaller), padded at the end with positions that neither
        decay nor write."""
        b, t = q.shape[:2]
        if t == 1:
            with jax.named_scope("kda.step"):
                o, state = self._step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0], state)
            return o[:, None], state
        c = min(CHUNK, 1 << (t - 1).bit_length())
        pad = -t % c

        def cut(x):     # [b, t, H, ...] -> [chunks, b, H, c, ...]
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            x = x.reshape(b, (t + pad) // c, c, *x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

        def chunk(s, inp):
            with jax.named_scope("kda.chunk"):
                o, s = self._chunk(*inp, s)
            return s, o

        with jax.named_scope("kda.scan"):
            state, o = jax.lax.scan(chunk, state, (cut(q), cut(k), cut(v),
                                                   cut(g), cut(beta)))
        o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)     # [b, n, c, H, D]
        return o.reshape(b, t + pad, *o.shape[3:])[:, :t], state

    @staticmethod
    def _step(q, k, v, g, beta, state):
        """One position: ``q``, ``k``, ``v``, ``g`` ``[b, H, D]``, ``beta
        [b, H]``. Elementwise products and sums over the state, in
        float32."""
        state = jnp.exp(g)[..., None] * state              # Diag(exp g) S
        read = jnp.sum(k[..., None] * state, axis=-2)       # k^T S  [b, H, D]
        state = state + (beta[..., None, None] * k[..., None]
                         * (v - read)[..., None, :])
        return jnp.sum(q[..., None] * state, axis=-2), state

    @staticmethod
    def _chunk(q, k, v, g, beta, s0):
        """One chunk in the UT form (module docstring): ``q``, ``k``,
        ``v``, ``g`` ``[b, H, c, D]``, ``beta [b, H, c]``, ``s0 [b, H, D,
        D]``. Every ``exp`` is of a sum of log-decays that is <= 0."""
        gam = jnp.cumsum(g, axis=-2)                       # [b, H, c, D]
        a = _decayed_pairs(beta[..., None] * k, k, gam, inclusive=False)
        rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(gam), v], -1)
        wu = jax.lax.linalg.triangular_solve(
            a, rhs, left_side=True, lower=True, unit_diagonal=True)
        w, u = jnp.split(wu, 2, axis=-1)
        u = u - jnp.einsum("bhck,bhkv->bhcv", w, s0, precision=_HIGHEST)
        pairs = _decayed_pairs(q, k, gam, inclusive=True)
        o = (jnp.einsum("bhck,bhkv->bhcv", q * jnp.exp(gam), s0,
                        precision=_HIGHEST)
             + jnp.einsum("bhts,bhsv->bhtv", pairs, u, precision=_HIGHEST))
        last = gam[..., -1:, :]                            # [b, H, 1, D]
        s1 = (jnp.exp(last)[..., 0, :, None] * s0
              + jnp.einsum("bhck,bhcv->bhkv", k * jnp.exp(last - gam), u,
                           precision=_HIGHEST))
        return o, s1

    # ---- entry points --------------------------------------------------

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        streaming = (not train and mask is None and state is not None
                     and "h" in state)
        if streaming:
            tail, s = state["h"], state["c"]
        else:
            tail, s = self._zero_state(x.shape[0], policy)
        out, tail, s = self._mix(params, x, tail, s, None, policy)
        out = self._act(self.activation or "identity")(out)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        if streaming:
            return out, {**state, "h": tail, "c": s}
        return out, state

    def step(self, params, x_t, state, *, policy=None):
        """Single timestep for streaming inference (the recurrence step)."""
        out, new_state = self.apply(params, x_t[:, None, :], state=state,
                                    policy=policy)
        return out[:, 0, :], new_state

    def apply_paged(self, params, x, conv_state, state, lane_ids, valid,
                    fresh, *, policy=None):
        """The serving engine's step, under ``Mamba2Mixer.apply_paged``'s
        contract: ``x [S, t_new, n_in]`` for the ``S`` lanes of a dispatch,
        whose states are rows ``lane_ids [S]`` of ``conv_state [lanes, K-1,
        3·H·D]`` and ``state [lanes, H, D, D]`` (an id of ``lanes`` or more
        is a padded slot of the bucket: it reads zeros and writes nothing);
        ``valid [S, t_new]``: a lane's padded positions advance neither
        state; ``fresh [S]``: the lane starts a sequence, from zero state;
        a lane with no valid position keeps its state bit for bit. Returns
        ``(out, conv_state, state)``. ``lane_ids=None``: the two arrays ARE
        the dispatch's rows (a fused block gathers once around its
        steps)."""
        policy = policy or _dtypes.default_policy()
        if lane_ids is None:
            tail, s = conv_state, state
        else:
            tail, s = gather_lanes(conv_state, state, lane_ids)
        keep = jnp.logical_not(fresh)
        tail0 = jnp.where(keep[:, None, None], tail, 0.0)
        s0 = jnp.where(keep[:, None, None, None], s, 0.0)
        out, tail1, s1 = self._mix(params, x, tail0, s0, valid, policy)
        out = self._act(self.activation or "identity")(out)
        live = jnp.any(valid, axis=1)
        tail1 = jnp.where(live[:, None, None], tail1, tail)
        s1 = jnp.where(live[:, None, None, None], s1, s)
        if lane_ids is None:
            return out, tail1, s1
        return (out, *scatter_lanes(conv_state, state, lane_ids, tail1, s1))
