"""State-space sequence mixing as a config-DSL layer: the Mamba-2 mixer.

No reference analog. The mixer of the hybrid decoder families
(``nemotron_h``: Mamba-2 layers between a few attention layers): a gated
selective state-space recurrence whose per-sequence memory is a FIXED-SIZE
state, whatever the context length, where attention's grows a K/V row a
token. With ``H`` heads of ``P`` channels, ``G`` groups of ``N`` state
columns and a depthwise causal convolution of width ``K`` over
``C = H·P + 2·G·N`` channels (``u`` is the layer's input, one row a token):

    [z | xBC | dt] = u @ W_in                      (H·P, C, H columns)
    xBC_t  <- silu(sum_k conv_w[:, k] · xBC_{t-K+1+k} + conv_b)
    x, B, C = split(xBC)                           ([H, P], [G, N], [G, N])
    dt_t   = softplus(dt_t + dt_bias);   A = -exp(A_log)
    S_t[h] = exp(dt_t[h]·A[h]) · S_{t-1}[h] + dt_t[h] · outer(x_t[h], B_t[g(h)])
    y_t[h] = S_t[h] @ C_t[g(h)] + D[h] · x_t[h]
    y      = group_rms_norm(y · silu(z)) · norm_g  (the gate comes first)
    out    = y @ W_out

Carried from token to token: the last ``K - 1`` rows of the
PRE-convolution ``xBC`` and ``S`` (``[H, P, N]``), both float32. Three
entry points compute the same numbers:

- :meth:`Mamba2Mixer.apply` over a whole sequence: the chunked scan (SSD,
  Dao & Gu 2024) in chunks of ``chunk_size``; inside a chunk the
  recurrence is two matrix products under a decay mask, between chunks it
  is carried by ``lax.scan``.
- the dense streaming contract of ``rnn_time_step`` / ``generate()``:
  ``state={"h": tail, "c": S}`` in, the same out (``_zero_state``).
- :meth:`Mamba2Mixer.apply_paged` for the serving engine: a chunk of
  ``t_new`` positions for ``S`` lanes whose states live in per-lane arrays
  of the engine's state arena; positions where ``valid`` is false advance
  neither state, and ``t_new`` = 1 is the recurrence step.

What stays float32 under a bf16 compute policy: ``dt``, ``A``, the decay,
the convolution, the state and the scan's sums, the norm's statistics. The
two projections run in the compute dtype.

Scopes (metadata only): ``ssm.in_proj``, ``ssm.conv``, ``ssm.scan``,
``ssm.norm_out`` (the gated norm and the output projection).
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


@register_layer("mamba2")
@dataclasses.dataclass
class Mamba2Mixer(BaseRecurrentLayer):
    """Mamba-2 mixer: [b, t, n_in] -> [b, t, n_in] (see module docstring).

    Params: ``W_in`` [n_in, 2·H·P + 2·G·N + H], ``conv_w`` [C, K],
    ``conv_b`` [C], ``dt_bias``, ``A_log``, ``D`` [H], ``norm_g`` [H·P],
    ``W_out`` [H·P, n_in].
    """

    n_heads: int = 8              # H
    head_dim: int = 16            # P
    n_groups: int = 1             # G: heads h // (H // G) share B and C
    state_size: int = 16          # N
    conv_kernel: int = 4          # K
    chunk_size: int = 128
    norm_eps: float = 1e-5
    dt_min: float = 0.001
    dt_max: float = 0.1
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
        return self.d_inner + 2 * self.n_groups * self.state_size

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        super().set_n_in(input_type, override)
        if self.n_out is None:
            self.n_out = self.n_in
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_heads={self.n_heads} not divisible by "
                             f"n_groups={self.n_groups}")

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        h, c = self.n_heads, self.conv_channels
        return {"W_in": (self.n_in, self.d_inner + c + h),
                "conv_w": (c, self.conv_kernel), "conv_b": (c,),
                "dt_bias": (h,), "A_log": (h,), "D": (h,),
                "norm_g": (self.d_inner,),
                "W_out": (self.d_inner, self.n_out)}

    def regularized_params(self) -> Tuple[str, ...]:
        return ("W_in", "W_out")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        shapes = self.param_shapes()
        k_in, k_out, k_conv, k_dt, k_a = jax.random.split(key, 5)
        wi = self.weight_init or "XAVIER"
        # Mamba's own start: dt log-uniform on [dt_min, dt_max] (stored as
        # the inverse softplus), A uniform on [1, 16]
        step = jnp.exp(jax.random.uniform(
            k_dt, (self.n_heads,), jnp.float32, math.log(self.dt_min),
            math.log(self.dt_max)))
        bound = 1.0 / math.sqrt(self.conv_kernel)
        return {
            "W_in": init_weights(k_in, shapes["W_in"], wi, fan_in=self.n_in,
                                 fan_out=shapes["W_in"][1],
                                 distribution=self.dist, dtype=dt),
            "conv_w": jax.random.uniform(k_conv, shapes["conv_w"],
                                         jnp.float32, -bound,
                                         bound).astype(dt),
            "conv_b": jnp.zeros(shapes["conv_b"], dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "A_log": jnp.log(jax.random.uniform(
                k_a, (self.n_heads,), jnp.float32, 1.0, 16.0)).astype(dt),
            "D": jnp.ones((self.n_heads,), dt),
            "norm_g": jnp.ones((self.d_inner,), dt),
            "W_out": init_weights(k_out, shapes["W_out"], wi,
                                  fan_in=self.d_inner, fan_out=self.n_out,
                                  distribution=self.dist, dtype=dt),
        }

    # ---- state -------------------------------------------------------

    def state_shapes(self, lanes: int):
        """(convolution tail, SSM state) of ``lanes`` sequences."""
        return ((lanes, self.conv_kernel - 1, self.conv_channels),
                (lanes, self.n_heads, self.head_dim, self.state_size))

    def paged_entry(self, lanes: int):
        """The arena's entry: every lane's tail and SSM state."""
        from ...serving.kv_cache import PagedEntry
        return PagedEntry(lane=self.state_shapes(lanes))

    def _zero_state(self, batch, policy):
        """The dense streaming carry: ``h`` is the convolution's tail,
        ``c`` the SSM state; both at least float32."""
        dt = jnp.promote_types(policy.compute_dtype, jnp.float32)
        tail, ssm = self.state_shapes(batch)
        return jnp.zeros(tail, dt), jnp.zeros(ssm, dt)

    # ---- the computation ---------------------------------------------

    def _mix(self, params, x, tail, ssm, valid, policy):
        """``x [b, t, n_in]`` from the states ``tail [b, K-1, C]`` and
        ``ssm [b, H, P, N]``: returns ``(out, tail, ssm)``. ``valid``
        (``[b, t]`` bool or None) must be a prefix mask in each row: the
        positions after a row's last valid one are padding, whose content
        reaches neither state."""
        b, t, _ = x.shape
        h, p, g, n = (self.n_heads, self.head_dim, self.n_groups,
                      self.state_size)
        sdt = ssm.dtype
        with jax.named_scope("ssm.in_proj"):
            xc, w_in = policy.cast_to_compute(x, params["W_in"])
            z, xbc, dt = jnp.split(
                xc @ w_in, [self.d_inner, self.d_inner + self.conv_channels],
                axis=-1)
        with jax.named_scope("ssm.conv"):
            xbc = xbc.astype(sdt)
            if valid is not None:
                xbc = jnp.where(valid[:, :, None], xbc, 0.0)
            cat = jnp.concatenate([tail, xbc], axis=1)     # [b, K-1+t, C]
            w = params["conv_w"].astype(sdt)
            conv = params["conv_b"].astype(sdt) + sum(
                w[:, k] * cat[:, k:k + t] for k in range(self.conv_kernel))
            conv = jax.nn.silu(conv)
            # the tail after this call: the K-1 rows before the first
            # padded position
            n_valid = (jnp.full((b,), t) if valid is None
                       else jnp.sum(valid, axis=1))
            rows = n_valid[:, None] + jnp.arange(self.conv_kernel - 1)
            tail = jnp.take_along_axis(cat, rows[:, :, None], axis=1)
        with jax.named_scope("ssm.scan"):
            xs, bm, cm = jnp.split(conv, [self.d_inner,
                                          self.d_inner + g * n], axis=-1)
            xs = xs.reshape(b, t, h, p)
            bm = bm.reshape(b, t, g, n)
            cm = cm.reshape(b, t, g, n)
            dt = jax.nn.softplus(dt.astype(sdt)
                                 + params["dt_bias"].astype(sdt))
            if valid is not None:     # a padded step: decay 1, no input
                dt = jnp.where(valid[:, :, None], dt, 0.0)
            a = -jnp.exp(params["A_log"].astype(sdt))      # [H]
            y, ssm = self._scan(xs, bm, cm, dt, a, ssm)
            y = y + params["D"].astype(sdt)[:, None] * xs
        with jax.named_scope("ssm.norm_out"):
            y = y.reshape(b, t, self.d_inner) * jax.nn.silu(z.astype(sdt))
            yg = y.reshape(b, t, g, self.d_inner // g)
            yg = yg * jax.lax.rsqrt(
                jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                + self.norm_eps)
            y = yg.reshape(b, t, self.d_inner) * params["norm_g"].astype(sdt)
            yc, w_out = policy.cast_to_compute(y, params["W_out"])
            out = yc @ w_out
        return out, tail, ssm

    def _scan(self, x, bm, cm, dt, a, ssm):
        """The recurrence over ``t`` positions from state ``ssm``: ``x
        [b, t, H, P]``, ``bm``/``cm [b, t, G, N]``, ``dt [b, t, H]`` (0 at a
        padded position), ``a [H]``. Returns ``(y [b, t, H, P], ssm)``.
        More than ``chunk_size`` positions are cut into chunks, padded at
        the end, and the state is carried between them by ``lax.scan``."""
        b, t = x.shape[:2]
        c = self.chunk_size
        if t <= c:
            return self._chunk(x, bm, cm, dt, a, ssm)
        pad = -t % c

        def cut(v):     # [b, t, ...] -> [chunks, b, c, ...]
            v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            v = v.reshape(b, (t + pad) // c, c, *v.shape[2:])
            return jnp.moveaxis(v, 1, 0)

        def step(state, chunk):
            y, state = self._chunk(*chunk, a, state)
            return state, y

        ssm, ys = jax.lax.scan(step, ssm, (cut(x), cut(bm), cut(cm),
                                           cut(dt)))
        y = jnp.moveaxis(ys, 0, 1).reshape(b, t + pad, *x.shape[2:])
        return y[:, :t], ssm

    def _chunk(self, x, bm, cm, dt, a, ssm):
        """One chunk of ``l`` positions in closed form. With ``cs_t`` the
        running sum of ``dt·A`` (the log of the decay since the chunk's
        start): what the carried state adds is ``exp(cs_t) · S @ C_t``, what
        the chunk's own inputs add is a causal ``l x l`` product of ``C_t ·
        B_s`` under the decay ``exp(cs_t - cs_s)``, and the state after
        the chunk decays by ``exp(cs_l)`` and takes in every input under
        ``exp(cs_l - cs_s)``. All decays are of sums that are <= 0."""
        hp = self.n_heads // self.n_groups
        l = x.shape[1]
        da = dt * a                                        # [b, l, H] <= 0
        cs = jnp.cumsum(da, axis=1)
        xdt = x * dt[..., None]                            # [b, l, H, P]
        if l == 1:
            # the recurrence step itself: no l x l product to form
            decay = jnp.exp(da[:, 0])[:, :, None, None]    # [b, H, 1, 1]
            ssm = decay * ssm + jnp.einsum(
                "bhp,bhn->bhpn", xdt[:, 0], jnp.repeat(bm[:, 0], hp, axis=1))
            y = jnp.einsum("bhpn,bhn->bhp", ssm,
                           jnp.repeat(cm[:, 0], hp, axis=1))
            return y[:, None], ssm
        bh = jnp.repeat(bm, hp, axis=2)                    # [b, l, H, N]
        ch = jnp.repeat(cm, hp, axis=2)
        # the carried state's part
        y = jnp.einsum("blhn,bhpn->blhp", ch, ssm) * jnp.exp(cs)[..., None]
        # the chunk's own inputs: position s reaches position t >= s
        diff = cs[:, :, None, :] - cs[:, None, :, :]       # [b, t, s, H]
        causal = jnp.tril(jnp.ones((l, l), bool))[None, :, :, None]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        cb = jnp.einsum("bthn,bshn->btsh", ch, bh)
        y = y + jnp.einsum("btsh,bshp->bthp", cb * decay, xdt)
        # the state after the chunk
        left = jnp.exp(cs[:, -1:, :] - cs)                 # [b, l, H]
        ssm = (jnp.exp(cs[:, -1])[:, :, None, None] * ssm
               + jnp.einsum("bshp,bshn->bhpn", xdt * left[..., None], bh))
        return y, ssm

    # ---- entry points --------------------------------------------------

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        streaming = (not train and mask is None and state is not None
                     and "h" in state)
        if streaming:
            tail, ssm = state["h"], state["c"]
        else:
            tail, ssm = self._zero_state(x.shape[0], policy)
        out, tail, ssm = self._mix(params, x, tail, ssm, None, policy)
        out = self._act(self.activation or "identity")(out)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        if streaming:
            return out, {**state, "h": tail, "c": ssm}
        return out, state

    def step(self, params, x_t, state, *, policy=None):
        """Single timestep for streaming inference (the recurrence step)."""
        out, new_state = self.apply(params, x_t[:, None, :], state=state,
                                    policy=policy)
        return out[:, 0, :], new_state

    def apply_paged(self, params, x, conv_state, ssm_state, lane_ids, valid,
                    fresh, *, policy=None):
        """The serving engine's step: ``x [S, t_new, n_in]`` for the ``S``
        lanes of a dispatch, whose states are rows ``lane_ids [S]`` of
        ``conv_state [lanes, K-1, C]`` and ``ssm_state [lanes, H, P, N]``
        (an id of ``lanes`` or more is a padded slot of the bucket: it
        reads zeros and writes nothing). ``valid [S, t_new]``: a lane's
        padded positions (and every position of a retired lane) advance
        neither state; ``fresh [S]``: the lane starts a sequence, from
        zero state. Returns ``(out, conv_state, ssm_state)``: the arrays
        whole, the dispatch's rows replaced.

        ``lane_ids=None``: the two arrays ARE the dispatch's rows (``[S,
        ...]``, gathered by the caller, who scatters them back): what a
        fused block does once around its steps, instead of a gather and a
        scatter of every lane's state at every step."""
        policy = policy or _dtypes.default_policy()
        if lane_ids is None:
            tail, ssm = conv_state, ssm_state
        else:
            tail, ssm = gather_lanes(conv_state, ssm_state, lane_ids)
        keep = jnp.logical_not(fresh)
        tail0 = jnp.where(keep[:, None, None], tail, 0.0)
        ssm0 = jnp.where(keep[:, None, None, None], ssm, 0.0)
        out, tail1, ssm1 = self._mix(params, x, tail0, ssm0, valid, policy)
        out = self._act(self.activation or "identity")(out)
        # a lane with no valid position keeps its state bit for bit
        live = jnp.any(valid, axis=1)
        tail1 = jnp.where(live[:, None, None], tail1, tail)
        ssm1 = jnp.where(live[:, None, None, None], ssm1, ssm)
        if lane_ids is None:
            return out, tail1, ssm1
        return (out, *scatter_lanes(conv_state, ssm_state, lane_ids, tail1,
                                    ssm1))


def gather_lanes(conv_state, ssm_state, lane_ids):
    """Rows ``lane_ids`` of the two per-lane state arrays (the second None
    for a vertex of one); an id past the last lane (a padded slot of the
    bucket) reads zeros."""
    return tuple(None if s is None else jnp.take(
        s, lane_ids, axis=0, mode="fill", fill_value=0)
        for s in (conv_state, ssm_state))


def scatter_lanes(conv_state, ssm_state, lane_ids, tail, ssm):
    """The two arrays with rows ``lane_ids`` replaced; a padded slot's row
    is dropped."""
    return tuple(None if s is None else s.at[lane_ids].set(r, mode="drop")
                 for s, r in ((conv_state, tail), (ssm_state, ssm)))
