"""Attention ops: fused single-device attention + ring attention (context
parallelism over the ICI mesh).

The reference has NO attention/sequence-parallel machinery (LSTM era — see
SURVEY §2.9): this is the long-context north-star extension. Design follows
the public ring-attention recipe (blockwise online-softmax accumulation while
K/V blocks rotate around the `seq` mesh axis via ``ppermute``), so sequence
length scales with the number of chips while every matmul stays MXU-shaped.

Shapes: q/k/v are [batch, time, heads, head_dim] ("BTHD").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def dot_product_attention(q, k, v, *, causal: bool = False, mask=None,
                          scale: Optional[float] = None):
    """Standard softmax attention, single program. [b,t,h,d] → [b,t,h,d].

    mask: optional [b, t_kv] key-validity mask (1=attend).

    Calls route to the Pallas flash kernel (``ops.flash_attention``,
    key masks included) automatically at t ≥ 4096 on TPU — forward AND
    blockwise backward, ≥2× measured (PERF.md).
    ``DL4JTPU_FLASH_ATTENTION=1`` forces the kernel at any length, ``0``
    forces this XLA path."""
    from .flash_attention import flash_attention, flash_available
    if q.ndim == 4 and q.shape == k.shape == v.shape \
            and flash_available(q.shape, mask):
        ctx = active_sequence_sharding()
        # the ONE scope around the kernel calls: XLA names a Mosaic call
        # after the innermost scope, and the benchmark's flash metrics
        # find the calls as ``jvp…`` / ``transpose…`` (PERF.md section 7)
        with jax.named_scope("attn.flash"):
            if ctx is not None and ctx[1] is None and ctx[2] is not None:
                return _flash_over_batch(q, k, v, causal, scale, mask,
                                         mesh=ctx[0], batch_axis=ctx[2])
            return flash_attention(q, k, v, causal, scale, mask=mask)
    with jax.named_scope("attn.dense"):
        return _dense_attention(q, k, v, causal, mask, scale)


def _dense_attention(q, k, v, causal, mask, scale):
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(causal_mask[None, None], logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :] > 0, logits, -jnp.inf)
    # manual stable softmax so a query with NO attendable keys (all -inf —
    # e.g. leading padded step under a causal mask) outputs 0, not NaN;
    # same guard the ring path's _block_attend applies
    m = jnp.max(logits, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(jnp.isneginf(logits), 0.0, jnp.exp(logits - m_safe))
    weights = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _flash_over_batch(q, k, v, causal, scale, mask, *, mesh, batch_axis):
    """The flash kernel under a batch-sharded GSPMD step (data parallel):
    the TPU compiler will not partition a Mosaic kernel by itself, so the
    call is wrapped in a ``shard_map`` over the batch axis and every
    device runs the kernel on its own examples."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from .flash_attention import flash_attention
    spec = P(batch_axis, None, None, None)
    if mask is None:
        mask = jnp.ones(q.shape[:2], jnp.float32)
    # check_vma=False: see make_ring_attention
    return shard_map(
        lambda q, k, v, m: flash_attention(q, k, v, causal, scale, mask=m),
        mesh=mesh, in_specs=(spec, spec, spec, P(batch_axis, None)),
        out_specs=spec, check_vma=False)(
            q, k, v, jnp.asarray(mask, jnp.float32))


def _block_attend(q, k, v, m_prev, num_prev, den_prev, *, scale,
                  q_offset, k_offset, causal, key_mask=None):
    """One K/V block of online-softmax accumulation (flash-style).

    m/num/den carry the running max, weighted-value numerator, and
    normalizer. q_offset/k_offset are global time offsets of the local q
    block and current k block (for causal masking across ring hops).
    key_mask: optional [b, tk] validity of THIS k block's keys."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale   # [b,h,tq,tk]
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qi = q_offset + jnp.arange(tq)
        ki = k_offset + jnp.arange(tk)
        allow = qi[:, None] >= ki[None, :]
        logits = jnp.where(allow[None, None], logits, -jnp.inf)
    if key_mask is not None:
        logits = jnp.where(key_mask[:, None, None, :] > 0, logits,
                           -jnp.inf)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))   # [b,h,tq]
    # guard: rows with no allowed keys yet keep -inf max → exp(0)=1 issues;
    # use where to keep them at zero contribution
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(logits - m_safe[..., None])                 # [b,h,tq,tk]
    p = jnp.where(jnp.isneginf(logits), 0.0, p)
    correction = jnp.where(jnp.isneginf(m_prev), 0.0,
                           jnp.exp(m_prev - m_safe))
    num = (num_prev * correction[..., None]
           + jnp.einsum("bhqk,bkhd->bhqd", p, v))
    den = den_prev * correction + jnp.sum(p, axis=-1)
    return m_new, num, den


def ring_flash_available(t_local: int) -> bool:
    """Should ring attention run its hops through the Pallas flash kernel?

    Same trace-time contract as ``flash_attention.flash_available``: only
    where a kernel can run (``util.xla.kernel_mode`` — the TPU backend,
    or interpret mode when a caller asked for it, which is how CPU test
    meshes exercise the real carry/VJP protocol); then
    ``DL4JTPU_FLASH_ATTENTION=1`` forces the kernel-in-ring path at any
    length, ``0`` forces the JAX-level online-softmax block (the parity
    oracle), unset = auto — on for per-device shards of t_local ≥ 1024 on
    the TPU backend. Non-divisible t_local is handled by the flash path
    itself (end-of-shard padding under a key mask), so divisibility never
    forces the oracle."""
    import os
    from ..util.xla import kernel_mode
    flag = os.environ.get("DL4JTPU_FLASH_ATTENTION", "auto")
    mode = kernel_mode()
    if flag == "0" or mode is None:
        return False
    if flag == "1":
        return True
    return t_local >= 1024 and mode == "mosaic"


def ring_attention(q, k, v, *, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None, mask=None,
                   impl: Optional[str] = None):
    """Ring attention INSIDE a shard_map over `axis_name`.

    Each device holds a [b, t_local, h, d] shard of q/k/v (the global
    sequence is split over the mesh axis). K/V shards rotate around the ring
    with ``ppermute`` while each device accumulates its local queries'
    attention online — full-sequence attention without ever materializing
    the [t, t] matrix or gathering the sequence.

    ``mask``: optional [b, t_local] key-validity shard (1=attend) — it
    rotates around the ring WITH its K/V shard, so padded keys anywhere in
    the global sequence are excluded; fully-masked query rows output 0
    (same semantics as ``dot_product_attention``).

    ``impl``: ``"flash"`` runs every hop through the Pallas flash kernel
    (forward AND backward — see ``_ring_flash_attention``), ``"jax"``
    keeps the JAX-level online-softmax block below (the parity oracle),
    ``None`` routes via :func:`ring_flash_available` at trace time.
    """
    if impl is None:
        impl = "flash" if ring_flash_available(q.shape[1]) else "jax"
    if impl == "flash":
        return _ring_flash_attention(q, k, v, mask, axis_name=axis_name,
                                     causal=causal, scale=scale)
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    t_local = q.shape[1]
    b, _, h, _ = q.shape

    q32 = q.astype(jnp.float32)
    # derive accumulators from q so they carry the same varying-across-mesh
    # type as the loop body's outputs (shard_map vma consistency)
    base = jnp.moveaxis(q32[..., 0], 1, 2)                  # [b,h,t_local]
    m = jnp.full_like(base, -jnp.inf)
    num = jnp.zeros_like(jnp.moveaxis(q32, 1, 2))           # [b,h,t_local,d]
    den = jnp.zeros_like(base)
    q_offset = idx * t_local

    perm = [(i, (i + 1) % n) for i in range(n)]
    # mask is a trace-time condition: the unmasked ring keeps its original
    # 5-tuple carry (no extra ppermute riding the hot path)
    extra = () if mask is None else (mask.astype(jnp.float32),)

    def body(i, carry):
        m, num, den, k_blk, v_blk, *mk = carry
        # the block currently held came from device (idx - i) mod n
        src = jnp.mod(idx - i, n)
        k_offset = src * t_local
        m, num, den = _block_attend(
            q32, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32),
            m, num, den, scale=scale, q_offset=q_offset,
            k_offset=k_offset, causal=causal,
            key_mask=mk[0] if mk else None)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        mk = tuple(jax.lax.ppermute(x, axis_name, perm) for x in mk)
        return (m, num, den, k_blk, v_blk, *mk)

    m, num, den, *_ = jax.lax.fori_loop(
        0, n, body, (m, num, den, k, v, *extra))
    out = num / jnp.maximum(den[..., None], 1e-30)          # [b,h,tq,d]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [b,tq,h,d]


# --------------------------------------------------------------------------
# ring-flash: every hop through the Pallas flash kernel, fwd AND bwd
# --------------------------------------------------------------------------
#
# Protocol (see flash_attention.flash_attention_block): each device keeps an
# online-softmax carry (m, l, o) for its LOCAL queries; every visiting K/V
# shard is one flash-kernel call folded into the carry. Cross-hop causal
# masking needs no dynamic offsets inside the kernel — a hop pair
# (q from device ``idx``, k/v born on device ``src``) is entirely
# pre-diagonal (src < idx → plain non-causal kernel), on the diagonal
# (src == idx → causal kernel), or entirely post-diagonal (src > idx →
# skipped, no kernel at all), selected with ``lax.switch`` on the traced
# hop index. The backward is a SECOND ring over the same ``ppermute``
# permutation: dq accumulates locally from the per-hop flash backward
# kernels (P recomputed from the saved full-sequence lse), while dk/dv
# accumulators travel WITH their K/V shard and arrive home after the full
# rotation.


def _ring_hop_branches(q32, scale, block_q, interpret):
    """(full, diag, skip) forward-hop branches for ``lax.switch``."""
    from .flash_attention import flash_attention_block

    def full(c, kb, vb, mb):
        return flash_attention_block(q32, kb, vb, c, causal=False,
                                     scale=scale, mask=mb, block_q=block_q,
                                     interpret=interpret)

    def diag(c, kb, vb, mb):
        return flash_attention_block(q32, kb, vb, c, causal=True,
                                     scale=scale, mask=mb, block_q=block_q,
                                     interpret=interpret)

    def skip(c, kb, vb, mb):
        return c

    return full, diag, skip


def _ring_flash_fwd_impl(q, k, v, mask, axis_name, causal, scale, block_q,
                         interpret):
    from .flash_attention import flash_carry_finalize, flash_carry_init
    n = jax.lax.psum(1, axis_name)
    # axis_index only when the hop trichotomy needs it: a dangling
    # partition-id in the non-causal program trips the CPU SPMD
    # partitioner (PartitionId outside a recognized manual region)
    idx = jax.lax.axis_index(axis_name) if causal else 0
    q32 = q.astype(jnp.float32)
    full, diag, skip = _ring_hop_branches(q32, scale, block_q, interpret)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, st):
        c, kb, vb, mb = st
        src = jnp.mod(idx - i, n)
        if causal:
            branch = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
            c = jax.lax.switch(branch, (full, diag, skip), c, kb, vb, mb)
        else:
            c = full(c, kb, vb, mb)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        mb = jax.lax.ppermute(mb, axis_name, perm)
        return c, kb, vb, mb

    carry, *_ = jax.lax.fori_loop(
        0, n, body, (flash_carry_init(q32), k, v, mask))
    out32, lse = flash_carry_finalize(carry)
    return out32, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ring_flash_core(q, k, v, mask, axis_name, causal, scale, block_q,
                     interpret):
    out32, _ = _ring_flash_fwd_impl(q, k, v, mask, axis_name, causal,
                                    scale, block_q, interpret)
    return out32.astype(q.dtype)


def _ring_flash_fwd_rule(q, k, v, mask, axis_name, causal, scale, block_q,
                         interpret):
    out32, lse = _ring_flash_fwd_impl(q, k, v, mask, axis_name, causal,
                                      scale, block_q, interpret)
    return out32.astype(q.dtype), (q, k, v, mask, out32, lse)


def _ring_flash_bwd_rule(axis_name, causal, scale, block_q, interpret,
                         res, g):
    from .flash_attention import flash_attention_bwd_block
    q, k, v, mask, out32, lse = res
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name) if causal else 0  # see fwd note
    q32 = q.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(kb, vb, mb, diag):
        return flash_attention_bwd_block(
            q32, kb.astype(jnp.float32), vb.astype(jnp.float32), out32,
            lse, g32, causal=diag, scale=scale, mask=mb, block_q=block_q,
            interpret=interpret)

    def full(kb, vb, mb):
        return hop(kb, vb, mb, False)

    def diag(kb, vb, mb):
        return hop(kb, vb, mb, True)

    def skip(kb, vb, mb):
        z = jnp.zeros_like(q32)
        return z, jnp.zeros_like(z), jnp.zeros_like(z)

    def body(i, st):
        dq, dk, dv, kb, vb, mb = st
        src = jnp.mod(idx - i, n)
        if causal:
            branch = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
            dq_h, dk_h, dv_h = jax.lax.switch(
                branch, (full, diag, skip), kb, vb, mb)
        else:
            dq_h, dk_h, dv_h = full(kb, vb, mb)
        dq = dq + dq_h.astype(jnp.float32)
        dk = dk + dk_h.astype(jnp.float32)
        dv = dv + dv_h.astype(jnp.float32)
        # dk/dv accumulators travel WITH their shard: after the full
        # rotation each lands back on its home device, complete
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        mb = jax.lax.ppermute(mb, axis_name, perm)
        dk = jax.lax.ppermute(dk, axis_name, perm)
        dv = jax.lax.ppermute(dv, axis_name, perm)
        return dq, dk, dv, kb, vb, mb

    zeros = jnp.zeros_like(q32)
    dq, dk, dv, *_ = jax.lax.fori_loop(
        0, n, body, (zeros, jnp.zeros_like(zeros), jnp.zeros_like(zeros),
                     k, v, mask))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros_like(mask))


_ring_flash_core.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def _ring_flash_attention(q, k, v, mask, *, axis_name: str, causal: bool,
                          scale: Optional[float],
                          block_q: Optional[int] = None):
    """Flash-kernel ring attention on the LOCAL shards (inside shard_map).

    Handles ragged shards here, outside the custom VJP: t_local that does
    not divide the flash tile is padded at the END of every shard (keys
    masked out, query rows sliced off after), which preserves global
    causal order because the hop trichotomy (pre/diagonal/post) only
    compares shard indices. ``interpret`` is resolved at trace time from
    ``util.xla.kernel_mode`` (compiled unless a caller asked otherwise)."""
    from ..util.xla import kernel_mode
    t_local = q.shape[1]
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / float(d) ** 0.5
    interpret = kernel_mode() == "interpret"
    bq = block_q or (128 if t_local >= 128 else -(-t_local // 8) * 8)
    pad = (-t_local) % bq
    if mask is None:
        mask = jnp.ones((q.shape[0], t_local), jnp.float32)
    mask = jnp.asarray(mask, jnp.float32)
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    out = _ring_flash_core(q, k, v, mask, axis_name, causal, scale, bq,
                           interpret)
    return out[:, :t_local] if pad else out


def make_ring_attention(mesh, axis_name: str = "seq", *,
                        causal: bool = False, batch_axis: Optional[str] = None,
                        with_mask: bool = False):
    """shard_map-wrapped ring attention: takes GLOBAL [b, t, h, d] arrays
    sharded (or shardable) over `axis_name` on the time axis, returns the
    global attention output with the same sharding.

    ``batch_axis``: optional mesh axis the BATCH dim is data-parallel over
    (2-D dp x sp meshes) — each dp slice runs its own independent ring over
    ``axis_name``; without it a dp-sharded batch would be gathered.

    ``with_mask=True`` returns ``fn(q, k, v, mask)`` where mask is the
    GLOBAL [b, t] key-validity array (sharded over ``axis_name`` like the
    time axis); mask shards rotate around the ring with their K/V."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axis, axis_name, None, None)
    mspec = P(batch_axis, axis_name)
    # check_vma=False: the flash route's pallas_call has no shard_map
    # replication rule (the ring touches no replicated operands anyway —
    # everything it moves is axis-sharded)
    smap = functools.partial(shard_map, mesh=mesh, check_vma=False)

    if with_mask:
        @functools.partial(smap, in_specs=(spec, spec, spec, mspec),
                           out_specs=spec)
        def fn(q, k, v, mask):
            return ring_attention(q, k, v, axis_name=axis_name,
                                  causal=causal, mask=mask)
        return fn

    @functools.partial(smap, in_specs=(spec, spec, spec), out_specs=spec)
    def fn(q, k, v):
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal)

    return fn


# --------------------------------------------------------------------------
# sequence-sharding context: how DSL layers discover an active seq mesh
# --------------------------------------------------------------------------

_SEQ_SHARDING: Optional[tuple] = None


class sequence_sharding:
    """Trace-time context that tells the attention ops how the step being
    traced is sharded over ``mesh``. With a ``seq_axis`` it routes
    ``SelfAttentionLayer`` (and any other time-mixing op that opts in) to
    ring attention over that axis. With ``seq_axis=None`` and a
    ``batch_axis`` (a data-parallel step) attention stays local to each
    example, and only the Pallas kernel route changes: it runs inside a
    ``shard_map`` over the batch axis (``_flash_over_batch``).

    Usage — activate around the *trace* of a step function::

        with sequence_sharding(mesh, "seq", batch_axis="dp"):
            loss = jax.jit(step)(params, x, y)   # first call traces here

    The context is read at trace time (like the flash-attention flag): the
    chosen route is baked into the compiled program, which is exactly what
    a sharded trainer wants — its step is always ring-routed, while the
    same model object used outside the context keeps its single-device
    program.
    """

    def __init__(self, mesh, seq_axis: Optional[str] = "seq",
                 batch_axis: Optional[str] = None):
        self.value = (mesh, seq_axis, batch_axis)

    def __enter__(self):
        global _SEQ_SHARDING
        self._prev = _SEQ_SHARDING
        _SEQ_SHARDING = self.value
        return self

    def __exit__(self, *exc):
        global _SEQ_SHARDING
        _SEQ_SHARDING = self._prev
        return False


def active_sequence_sharding() -> Optional[tuple]:
    """(mesh, seq_axis, batch_axis) if a sequence_sharding context is
    active, else None."""
    return _SEQ_SHARDING
