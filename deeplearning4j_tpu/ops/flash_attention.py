"""Pallas flash-attention for TPU — forward AND backward kernels.

Forward: the [t, t] score matrix never exists anywhere. The grid holds one
[block_q, block_k] logits tile at a time; per-q-block online-softmax
accumulators live in VMEM. Two variants auto-dispatched on K/V size:
whole-K/V-in-VMEM with a dynamic fori_loop that SKIPS post-diagonal blocks
(loads and compute) in the causal case, and a grid-streamed variant
(O(block) VMEM) for longer sequences. The kernel also emits the per-row
log-sum-exp, which makes the backward blockwise too.

Key-validity masks ([b, t_kv], 1=attend) are supported: masked keys get
NEG_INF logits, and rows with NO attendable keys (leading padding under a
causal mask, all-zero mask rows) output 0 — same semantics as the guarded
XLA path in ``ops.attention``.

Backward: ONE Pallas call a layer (``_bwd_kernel``). On the
grid (heads, key blocks, query blocks) each [block_q, block_k] tile's P and
dS are recomputed once from the saved lse and feed all three gradients:
dk and dv accumulate in a [block_k, d] VMEM scratch over a key block's
steps, dq in a float32 scratch that holds the head's WHOLE [t, d] dq (2 MiB
at t 8192, d 64), zeroed at the head's first step and written at its last
to an output block that stays resident in between. That call runs where
the head's dq fits ``_VMEM_DQ_LIMIT`` (t·d·4 bytes, compared at trace time;
every shape the tests, examples and the benchmark run does); a longer
sequence takes two calls: the same kernel without its dq for dk/dv, and
``_bwd_dq_kernel``, which computes every tile again.
Peak memory is O(t·block + t·d), so TRAINING runs at sequence lengths
where XLA's attention cannot even compile. Gradients match the dense path
(CPU interpret + on-chip parity, ``chip_smoke.py``). A JAX-blockwise
fallback backward remains behind ``DL4JTPU_FLASH_BWD=jax``.

What is measured lives in PERF.md, with the installation each figure was
taken on: the kernels compile under the installed TPU compiler and agree
with the XLA path on the v5e (``chip_smoke.py``); the one-call backward's
times against the two calls, tile by tile, are PR 36's (PERF.md section
6). The kernels' speed-up over XLA dates from before PR 1 on an
installation that no longer exists and has not been re-measured.

Routing (``ops.attention.dot_product_attention``): auto at t ≥ 4096 on
the TPU backend; ``DL4JTPU_FLASH_ATTENTION=1`` forces it on (any length),
``0`` forces the XLA path. Where no kernel can run (not a TPU, and no
caller asked for interpret mode: ``util.xla.kernel_mode``) every route
is the XLA path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_HALF_NEG = NEG_INF / 2
# whole-K/V-in-VMEM variant above this size switches to the grid-streamed
# kernel (module constant so tests can force the streamed path)
_VMEM_KV_LIMIT = 4 * 1024 * 1024
# the backward computes dq, dk and dv in ONE call while a head's float32
# dq, t * d * 4 bytes, fits this budget (it stays in a VMEM scratch for the
# head's whole sweep); above it the two-call backward runs
_VMEM_DQ_LIMIT = 4 * 1024 * 1024
# scoped-VMEM limit of every backward call: 1024 x 1024 tiles need more
# than the default 16 MiB, and the one call's dq scratch and resident
# output block come on top (a v5e core has 128 MiB)
_BWD_VMEM_BYTES = 64 * 1024 * 1024


def _masked_update(q, k, v, valid, m_prev, num, den, *, scale, causal,
                   block_q, block_k, q_offset, k_offset):
    """One online-softmax block update with NEG_INF-sentinel guards:
    rows whose running max is still NEG_INF (no attendable key yet)
    contribute exactly zero — so fully-masked rows end at num=den=0."""
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # [bq, bk]
    logits = jnp.where(valid, logits, NEG_INF)   # valid: [1, bk] bool
    if causal:
        rows = q_offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        logits = jnp.where(rows >= cols, logits, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    m_safe = jnp.where(m_new <= _HALF_NEG, 0.0, m_new)
    p = jnp.where(logits <= _HALF_NEG, 0.0,
                  jnp.exp(logits - m_safe[:, None]))
    corr = jnp.where(m_prev <= _HALF_NEG, 0.0,
                     jnp.exp(m_prev - m_safe))
    num = num * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    den = den * corr + jnp.sum(p, axis=-1)
    return m_new, num, den


def _finalize(m, num, den):
    """(out, lse) from the accumulators; 0-key rows → out 0, lse NEG_INF."""
    out = num / jnp.maximum(den, 1e-30)[:, None]
    lse = jnp.where(den > 0, m + jnp.log(jnp.maximum(den, 1e-30)), NEG_INF)
    return out, lse


# --------------------------------------------------------------------------
# forward kernels
# --------------------------------------------------------------------------


def _fwd_kernel_vmem(q_ref, k_ref, v_ref, mk_ref, o_ref, lse_ref, *,
                     scale, causal, block_q, block_k):
    """Whole-K/V-in-VMEM variant: one DMA brings K/V in, then a fori_loop
    over k-blocks runs the online softmax. The dynamic loop bound skips
    post-diagonal blocks entirely (loads and compute) when causal."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # [block_q, d]
    t = k_ref.shape[1]
    d = q.shape[-1]

    def body(j, carry):
        m_prev, num, den = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        valid = mk_ref[0, pl.ds(j, 1), :] > 0     # [1, block_k]
        return _masked_update(q, k, v, valid, m_prev, num, den,
                              scale=scale, causal=causal, block_q=block_q,
                              block_k=block_k, q_offset=qi * block_q,
                              k_offset=j * block_k)

    if causal:
        nk = (qi * block_q + block_q + block_k - 1) // block_k
    else:
        nk = t // block_k
    init = (jnp.full((block_q,), NEG_INF, jnp.float32),
            jnp.zeros((block_q, d), jnp.float32),
            jnp.zeros((block_q,), jnp.float32))
    m, num, den = jax.lax.fori_loop(0, nk, body, init)
    out, lse = _finalize(m, num, den)
    o_ref[0] = out.astype(o_ref.dtype)
    lse_ref[0, :, 0] = lse


def _fwd_kernel_stream(q_ref, k_ref, v_ref, mk_ref, o_ref, lse_ref, m_s,
                       num_s, den_s, *, scale, causal, block_q, block_k,
                       nk):
    """Grid-streamed variant: pallas double-buffers K/V blocks through
    VMEM; online-softmax accumulators persist in VMEM scratch across the
    (sequential) k dimension of the grid."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        num_s[...] = jnp.zeros_like(num_s)
        den_s[...] = jnp.zeros_like(den_s)

    relevant = (kj * block_k <= qi * block_q + block_q - 1) if causal \
        else (kj >= 0)

    @pl.when(relevant)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)              # [bq, d]
        k = k_ref[0].astype(jnp.float32)              # [bk, d]
        v = v_ref[0].astype(jnp.float32)              # [bk, d]
        valid = mk_ref[0, pl.ds(kj, 1), :] > 0    # [1, block_k]
        m, num, den = _masked_update(
            q, k, v, valid, m_s[...][:, 0], num_s[...], den_s[...][:, 0],
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            q_offset=qi * block_q, k_offset=kj * block_k)
        m_s[...] = m[:, None]
        num_s[...] = num
        den_s[...] = den[:, None]

    @pl.when(kj == nk - 1)
    def _final():
        out, lse = _finalize(m_s[...][:, 0], num_s[...], den_s[...][:, 0])
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0, :, 0] = lse


def _flash_fwd_btd(qt, kt, vt, mask_bt, *, n_heads, scale, causal,
                   block_q, interpret, block_k: int = 512,
                   auto_tile: bool = False):
    """[bh, t, d] q/k/v + [b, t] key mask → ([bh, t, d] out, [bh, t] lse).
    The mask is NOT head-folded: index maps read row ``bh // n_heads``, so
    one [b, ...] mask array serves every head."""
    bh, t, d = qt.shape
    if t % block_q:
        raise ValueError(
            f"flash_attention needs t % block_q == 0 (t={t}, "
            f"block_q={block_q}) — unwritten tail blocks would return "
            "uninitialized memory; use the XLA path for ragged lengths")
    if auto_tile:
        # default-tile callers get wider q tiles when t allows (512 rows
        # measured ~10% faster at f32-4096 and bf16-8192, d=128); an
        # EXPLICIT block_q is never overridden, and the upgrade is skipped
        # when the q/num tile would exceed ~512KB VMEM (large head dims)
        for wider in (512, 256):
            if (wider > block_q and t % wider == 0
                    and wider * d * 4 <= 512 * 1024):
                block_q = wider
                break
    if t % block_k:
        block_k = block_q
    nk = t // block_k
    # mask rides pre-blocked as [b, t//block_k, block_k]: each kernel step
    # slices one native (1, block_k) row — no vector reshapes (Mosaic
    # rejects rank changes), no lane padding ([bh, t, 1] OOM'd VMEM), no
    # lane-dim dynamic slicing ([bh, 1, t] measured ~10x slower). Both
    # variants take the FULL per-batch-row mask block (t floats — trivially
    # VMEM-resident) because a (1, 1, block_k) partial block would violate
    # the (8, 128)-or-full tiling rule on the middle dim.
    mkt = mask_bt.astype(jnp.float32).reshape(-1, nk, block_k)
    h_ = n_heads
    # lse rides as [bh, t, 1]: TPU block shapes need the last two dims
    # (8, 128)-aligned or full — (block_q, 1) satisfies that, (1, block_q)
    # does not
    out_shapes = (jax.ShapeDtypeStruct((bh, t, d), qt.dtype),
                  jax.ShapeDtypeStruct((bh, t, 1), jnp.float32))
    out_specs = (pl.BlockSpec((1, block_q, d), lambda b, i, *j: (b, i, 0)),
                 pl.BlockSpec((1, block_q, 1), lambda b, i, *j: (b, i, 0)))
    kv_bytes = 2 * t * d * qt.dtype.itemsize
    if kv_bytes <= _VMEM_KV_LIMIT:
        kernel = functools.partial(_fwd_kernel_vmem, scale=scale,
                                   causal=causal, block_q=block_q,
                                   block_k=block_k)
        out, lse = pl.pallas_call(
            kernel,
            grid=(bh, t // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, nk, block_k),
                             lambda b, i: (b // h_, 0, 0)),
            ],
            out_specs=out_specs,
            out_shape=out_shapes,
            interpret=interpret,
        )(qt, kt, vt, mkt)
        return out, lse[..., 0]
    kernel = functools.partial(_fwd_kernel_stream, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, nk=nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, t // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, nk, block_k), lambda b, i, j: (b // h_, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, d), jnp.float32),    # numerator
            pltpu.VMEM((block_q, 1), jnp.float32),    # denominator
        ],
        interpret=interpret,
    )(qt, kt, vt, mkt)
    return out, lse[..., 0]


# --------------------------------------------------------------------------
# blockwise backward (flash backward in plain JAX — tiles via lax.scan)
# --------------------------------------------------------------------------


def _flash_bwd_btd(q, k, v, mk, out, lse, dout, *, scale, causal, block_q,
                   block_k):
    """[bh, t, d] grads with O(t·block + t·d) peak memory.

    Standard flash backward: P recomputed per tile from the saved lse,
    dS = P ∘ (dout·vᵀ − Δ), Δ = rowsum(dout ∘ out). Two passes, each
    parallel (vmapped) over one block axis and sequential over the other,
    so XLA batches the tile matmuls instead of serializing them."""
    bh, t, d = q.shape
    if t % block_k:
        block_k = block_q
    nq, nk = t // block_q, t // block_k
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    delta = jnp.sum(f32(dout) * f32(out), axis=-1)        # [bh, t]
    i_base = jnp.arange(nq) * block_q
    j_base = jnp.arange(nk) * block_k
    r_iota = jnp.arange(block_q)
    c_iota = jnp.arange(block_k)

    def _p_ds(qi, kj, vj, mj, doi, lsei, deltai, i0, j0):
        """Recompute one [block_q, block_k] tile's P and dS. Rows with
        lse=NEG_INF (no attendable keys) get P=0, not exp(overflow)."""
        s = jnp.dot(qi, kj.T, preferred_element_type=jnp.float32) * scale
        lse_safe = jnp.where(lsei <= _HALF_NEG, 0.0, lsei)
        p = jnp.where((lsei <= _HALF_NEG)[:, None], 0.0,
                      jnp.exp(s - lse_safe[:, None]))
        p = jnp.where((mj > 0)[None, :], p, 0.0)
        if causal:
            allow = (i0 + r_iota)[:, None] >= (j0 + c_iota)[None, :]
            p = jnp.where(allow, p, 0.0)
        dp = jnp.dot(doi, vj.T, preferred_element_type=jnp.float32)
        ds = p * (dp - deltai[:, None]) * scale
        return p, ds

    def per_head(q, k, v, mk, lse, delta, dout):
        q_r = f32(q).reshape(nq, block_q, d)
        k_r = f32(k).reshape(nk, block_k, d)
        v_r = f32(v).reshape(nk, block_k, d)
        m_r = f32(mk).reshape(nk, block_k)
        do_r = f32(dout).reshape(nq, block_q, d)
        lse_r = lse.reshape(nq, block_q)
        dl_r = delta.reshape(nq, block_q)

        def dq_block(qi, doi, lsei, deltai, i0):
            def over_j(dqi, xs):
                kj, vj, mj, j0 = xs
                _, ds = _p_ds(qi, kj, vj, mj, doi, lsei, deltai, i0, j0)
                return dqi + jnp.dot(ds, kj,
                                     preferred_element_type=jnp.float32), None
            dqi, _ = jax.lax.scan(over_j,
                                  jnp.zeros((block_q, d), jnp.float32),
                                  (k_r, v_r, m_r, j_base))
            return dqi

        def dkv_block(kj, vj, mj, j0):
            def over_i(carry, xs):
                dkj, dvj = carry
                qi, doi, lsei, deltai, i0 = xs
                p, ds = _p_ds(qi, kj, vj, mj, doi, lsei, deltai, i0, j0)
                dkj = dkj + jnp.dot(ds.T, qi,
                                    preferred_element_type=jnp.float32)
                dvj = dvj + jnp.dot(p.T, doi,
                                    preferred_element_type=jnp.float32)
                return (dkj, dvj), None
            (dkj, dvj), _ = jax.lax.scan(
                over_i, (jnp.zeros((block_k, d), jnp.float32),
                         jnp.zeros((block_k, d), jnp.float32)),
                (q_r, do_r, lse_r, dl_r, i_base))
            return dkj, dvj

        dq = jax.vmap(dq_block)(q_r, do_r, lse_r, dl_r, i_base)
        dk, dv = jax.vmap(dkv_block)(k_r, v_r, m_r, j_base)
        return (dq.reshape(t, d), dk.reshape(t, d), dv.reshape(t, d))

    dq, dk, dv = jax.vmap(per_head)(q, k, v, mk, lse, delta, dout)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# Pallas backward kernels: one call for dq, dk and dv; above the dq budget
# the dk/dv pass and a dq pass
# --------------------------------------------------------------------------


def _bwd_p_ds(q, k, v, do, lse, delta, valid, *, scale, causal,
              q_offset, k_offset, block_q, block_k):
    """Recompute one [block_q, block_k] tile's (P, dS) from the saved lse
    (standard flash backward). Rows with lse=NEG_INF (no attendable keys)
    get P=0, not exp(overflow)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    lse_safe = jnp.where(lse <= _HALF_NEG, 0.0, lse)
    p = jnp.where((lse <= _HALF_NEG)[:, None], 0.0,
                  jnp.exp(s - lse_safe[:, None]))
    p = jnp.where(valid, p, 0.0)                    # valid: [1, bk] bool
    if causal:
        rows = q_offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        p = jnp.where(rows >= cols, p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, mk_ref, lse_ref, dl_ref, do_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k, nk):
    """dq pass of the two-call backward (a head whose dq is beyond
    ``_VMEM_DQ_LIMIT``): grid (bh, nq, nk), k sequential — the dq tile
    accumulates in VMEM scratch while Pallas streams (double-buffers) K/V
    blocks."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    relevant = (kj * block_k <= qi * block_q + block_q - 1) if causal \
        else (kj >= 0)

    @pl.when(relevant)
    def _accumulate():
        _, ds = _bwd_p_ds(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32), do_ref[0].astype(jnp.float32),
            lse_ref[0, :, 0], dl_ref[0, :, 0],
            mk_ref[0, pl.ds(kj, 1), :] > 0,
            scale=scale, causal=causal, q_offset=qi * block_q,
            k_offset=kj * block_k, block_q=block_q, block_k=block_k)
        dq_acc[...] += jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _write():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_kernel(k_ref, v_ref, mk_ref, q_ref, lse_ref, dl_ref, do_ref, *refs,
                scale, causal, block_q, block_k, nq, nk, with_dq):
    """The backward of one head: grid (bh, nk, nq), q sequential — P and
    dS are recomputed ONCE per tile and feed dk (dSᵀ·q), dv (Pᵀ·dout) and,
    ``with_dq``, dq (dS·k). dk/dv accumulate in a [block_k, d] scratch
    over a key block's steps; dq accumulates in a scratch that holds the
    head's WHOLE [t, d] dq, zeroed at the head's first step and written at
    its last to an output block that stays resident in between. Without
    ``with_dq`` (a head whose dq does not fit ``_VMEM_DQ_LIMIT``) this is
    the dk/dv pass and ``_bwd_dq_kernel`` computes every tile again."""
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if with_dq:
        @pl.when((kj == 0) & (qi == 0))
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    relevant = (qi * block_q + block_q - 1 >= kj * block_k) if causal \
        else (qi >= 0)

    @pl.when(relevant)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, ds = _bwd_p_ds(
            q, k, v_ref[0].astype(jnp.float32),
            do, lse_ref[0, :, 0], dl_ref[0, :, 0],
            mk_ref[0, pl.ds(kj, 1), :] > 0,
            scale=scale, causal=causal, q_offset=qi * block_q,
            k_offset=kj * block_k, block_q=block_q, block_k=block_k)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            dq_acc[rows, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if with_dq:
        @pl.when((kj == nk - 1) & (qi == nq - 1))
        def _write_dq():
            dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_btd_pallas(q, k, v, mk, out, lse, dout, *, scale, causal,
                          block_q, block_k, interpret, n_heads):
    """[bh, t, d] grads in Pallas. Same math as ``_flash_bwd_btd`` (the
    JAX-blockwise fallback, kept for ``DL4JTPU_FLASH_BWD=jax``) with the
    tile loops lowered to Mosaic. ONE call (``_bwd_kernel``) where a
    head's float32 dq fits ``_VMEM_DQ_LIMIT``: every tile's P and dS are
    computed once. Above it two calls: the same kernel for dk/dv, and
    ``_bwd_dq_kernel`` with a [block_q, d] accumulator."""
    bh, t, d = q.shape
    if t % block_k:
        block_k = block_q
    nq, nk = t // block_q, t // block_k
    h_ = n_heads
    fused = t * d * 4 <= _VMEM_DQ_LIMIT
    # delta = rowsum(dout * out): one cheap fused elementwise pass in XLA
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[..., None]                       # [bh, t, 1]
    lse3 = lse[..., None]                                     # [bh, t, 1]
    mkt = mk.astype(jnp.float32).reshape(-1, nk, block_k)

    # grid (bh, nk, nq): i (q-blocks) is the SEQUENTIAL (last) grid dim
    jk_spec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    if causal:
        # pre-diagonal q blocks contribute nothing to this k block —
        # clamp their index map to the first relevant block (fetched
        # once, then reused) so the skipped steps cost no DMA
        def _q_map(b, j, i):
            return (b, jnp.maximum(i, (j * block_k) // block_q), 0)
    else:
        def _q_map(b, j, i):
            return (b, i, 0)
    iq_spec = pl.BlockSpec((1, block_q, d), _q_map)
    iq_col = pl.BlockSpec((1, block_q, 1), _q_map)
    # the batch row's whole mask, whichever block axis the grid names first
    mk_spec = pl.BlockSpec((1, nk, block_k), lambda b, *_: (b // h_, 0, 0))
    dkv_shape = (jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                 jax.ShapeDtypeStruct((bh, t, d), v.dtype))
    dkv_acc = [pltpu.VMEM((block_k, d), jnp.float32),
               pltpu.VMEM((block_k, d), jnp.float32)]
    dq_shape = jax.ShapeDtypeStruct((bh, t, d), q.dtype)
    kernel = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, nq=nq,
                               nk=nk, with_dq=fused)
    in_specs = [jk_spec, jk_spec, mk_spec, iq_spec, iq_col, iq_col, iq_spec]
    vmem = pltpu.CompilerParams(vmem_limit_bytes=_BWD_VMEM_BYTES)
    if fused:
        # the head's whole dq: one block, resident across the head's
        # steps, written back once when the head changes
        return pl.pallas_call(
            kernel, grid=(bh, nk, nq), in_specs=in_specs,
            out_specs=(pl.BlockSpec((1, t, d), lambda b, j, i: (b, 0, 0)),
                       jk_spec, jk_spec),
            out_shape=(dq_shape,) + dkv_shape,
            scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)] + dkv_acc,
            compiler_params=vmem, interpret=interpret,
        )(k, v, mkt, q, lse3, delta, dout)
    dk, dv = pl.pallas_call(
        kernel, grid=(bh, nk, nq), in_specs=in_specs,
        out_specs=(jk_spec, jk_spec), out_shape=dkv_shape,
        scratch_shapes=dkv_acc, compiler_params=vmem, interpret=interpret,
    )(k, v, mkt, q, lse3, delta, dout)

    # dq pass: grid (bh, nq, nk), j (k-blocks) sequential
    i_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    i_col = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    if causal:
        # clamp the streamed K/V index map at the causal diagonal: the
        # grid still visits post-diagonal steps (compute is pl.when-gated
        # off), but a repeated block index lets Pallas elide the DMA
        def _kv_map(b, i, j):
            return (b, jnp.minimum(
                j, (i * block_q + block_q - 1) // block_k), 0)
    else:
        def _kv_map(b, i, j):
            return (b, j, 0)
    j_spec = pl.BlockSpec((1, block_k, d), _kv_map)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[i_spec, j_spec, j_spec, mk_spec, i_col, i_col, i_spec],
        out_specs=i_spec, out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=vmem, interpret=interpret,
    )(q, k, v, mkt, lse3, delta, dout)
    return dq, dk, dv


# --------------------------------------------------------------------------
# block-callable entry: online-softmax carry across flash calls
# --------------------------------------------------------------------------
#
# The ring sequence-parallel path (ops.attention.ring_attention) holds one
# local Q shard and sees K/V one visiting shard per hop.  These three
# functions let each hop run the SAME Pallas forward kernel on (local q,
# visiting k/v) and fold the hop's result into an online-softmax carry
# (running max ``m``, normalizer ``l``, accumulator ``o``), so the
# full-sequence softmax is exact without the [t, t] matrix ever existing —
# on any device, at any hop.  Cross-hop causal masking is resolved by the
# CALLER into one of two static kernel modes (every hop pair is either
# entirely pre-diagonal → ``causal=False``, on the diagonal →
# ``causal=True``, or entirely post-diagonal → skipped), so the kernels
# never need dynamic global offsets.


def flash_carry_init(q):
    """Fresh (m, l, o) carry for a [b, t, h, d] query block: running max
    ``m`` [b,t,h] at NEG_INF, normalizer ``l`` [b,t,h] at 0, accumulator
    ``o`` [b,t,h,d] at 0 — all float32 regardless of q's dtype (the carry
    is the accumulation domain)."""
    b, t, h, d = q.shape
    return (jnp.full((b, t, h), NEG_INF, jnp.float32),
            jnp.zeros((b, t, h), jnp.float32),
            jnp.zeros((b, t, h, d), jnp.float32))


def flash_attention_block(q, k, v, carry, *, causal=False, scale=None,
                          mask=None, block_q=None, interpret=False):
    """One carry update: flash-tiled attention of q [b,tq,h,d] against ONE
    k/v block [b,tk,h,d], folded into ``carry`` (from
    :func:`flash_carry_init` or a previous call).  The Pallas forward
    kernel does the tiled work and emits this block's (out, lse); the fold
    is the standard log-space online-softmax merge, exact and
    order-independent.

    ``causal=True`` means q and k/v occupy the SAME global time offset
    (the diagonal block); pre-diagonal blocks are ``causal=False`` and
    post-diagonal blocks must simply not be fed.  ``mask``: optional
    [b, tk] key-validity for THIS block.  Rows that have seen no
    attendable key anywhere keep m=NEG_INF / l=0 and finalize to 0."""
    m, l, o = carry
    b, t, h, d = q.shape
    if k.shape[1] != t:
        raise ValueError(
            f"flash_attention_block needs len(k) == len(q) (got "
            f"{k.shape[1]} vs {t}) — ring hops are shard-sized; pad the "
            "shorter side under a key mask instead")
    if mask is None:
        mask = jnp.ones((k.shape[0], k.shape[1]), jnp.float32)
    out_h, lse_h = _core_fwd(q, k, v, jnp.asarray(mask, jnp.float32),
                             causal, scale, block_q, interpret)
    lse_h = lse_h.reshape(b, h, t).transpose(0, 2, 1)       # [b, t, h]
    m_new = jnp.maximum(m, lse_h)
    m_safe = jnp.where(m_new <= _HALF_NEG, 0.0, m_new)
    corr = jnp.where(m <= _HALF_NEG, 0.0, jnp.exp(m - m_safe))
    w = jnp.where(lse_h <= _HALF_NEG, 0.0, jnp.exp(lse_h - m_safe))
    o = o * corr[..., None] + out_h.astype(jnp.float32) * w[..., None]
    l = l * corr + w
    return m_new, l, o


def flash_carry_finalize(carry):
    """(out [b,t,h,d] f32, lse [b,t,h] f32) from an (m, l, o) carry.
    Rows that never saw an attendable key → out 0, lse NEG_INF — the same
    semantics as the monolithic kernel."""
    m, l, o = carry
    out = o / jnp.maximum(l, 1e-30)[..., None]
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    return out, lse


def flash_attention_bwd_block(q, k, v, out, lse, dout, *, causal=False,
                              scale=None, mask=None, block_q=None,
                              interpret=False):
    """Per-block flash backward for the ring VJP: given the FINAL output,
    its cotangent, and the FULL-sequence lse (all [b,tq,h,...], from
    :func:`flash_carry_finalize`), return this (q, k/v)-block pair's
    (dq, dk, dv) contributions — the standard flash backward recomputes P
    per tile from the global lse, so per-block contributions sum exactly
    to the dense gradient.  Same Pallas kernels as the monolithic
    backward; ``DL4JTPU_FLASH_BWD=jax`` selects the lax.scan blockwise
    fallback (read at trace time, like the monolithic path).  ``causal``
    has the same diagonal-block meaning as :func:`flash_attention_block`."""
    import os
    b, t, h, d = q.shape
    if k.shape[1] != t:
        raise ValueError(
            f"flash_attention_bwd_block needs len(k) == len(q) (got "
            f"{k.shape[1]} vs {t}) — ring hops are shard-sized")
    s = _resolve_scale(scale, d)
    if mask is None:
        mask = jnp.ones((k.shape[0], k.shape[1]), jnp.float32)
    mask = jnp.asarray(mask, jnp.float32)
    to_btd = lambda a: a.transpose(0, 2, 1, 3).reshape(
        a.shape[0] * a.shape[2], a.shape[1], a.shape[3])
    lse_b = lse.transpose(0, 2, 1).reshape(b * h, t)
    use_jax = os.environ.get("DL4JTPU_FLASH_BWD") == "jax"
    bq_bwd, bk_bwd = _bwd_tiles(t, block_q, pallas=not use_jax)
    if use_jax:
        mk = jnp.repeat(mask, h, axis=0)
        dq, dk, dv = _flash_bwd_btd(
            to_btd(q), to_btd(k), to_btd(v), mk, to_btd(out), lse_b,
            to_btd(dout), scale=s, causal=causal, block_q=bq_bwd,
            block_k=bk_bwd)
    else:
        dq, dk, dv = _flash_bwd_btd_pallas(
            to_btd(q), to_btd(k), to_btd(v), mask, to_btd(out), lse_b,
            to_btd(dout), scale=s, causal=causal, block_q=bq_bwd,
            block_k=bk_bwd, interpret=interpret, n_heads=h)
    back = lambda a, tt: a.reshape(b, h, tt, d).transpose(0, 2, 1, 3)
    return back(dq, t), back(dk, k.shape[1]), back(dv, k.shape[1])


# --------------------------------------------------------------------------
# public op with custom_vjp
# --------------------------------------------------------------------------


def _resolve_scale(scale, d):
    return scale if scale is not None else 1.0 / float(d) ** 0.5


def _bwd_tiles(t, block_q, pallas):
    """Backward tile choice — ONE table for the monolithic VJP and the
    ring's per-hop backward, whichever backward runs: the one Pallas call,
    the two calls above ``_VMEM_DQ_LIMIT`` and the lax.scan fallback.

    1024 × 1024 where t allows, from a sweep on the v5e (my chip runs,
    PR 36; PERF.md section 6). At the training cell's shape,
    [32, 8192, 64] bf16 causal, the one call takes 11.90 ms at 1024²,
    12.15 at 256 × 2048, 12.25 at 512 × 1024, 12.42 at 512 × 2048, 12.61 at
    1024 × 2048, 12.79 at 1024 × 512, 13.2 at 2048 × 1024, 13.5 at 512²,
    15.7 at 256 × 512, 16.1 at 128 × 1024; the two calls take 16.61 ms at
    512 × 1024. 1024² also wins at [16, 8192, 128]
    (5.49 against 5.65 ms at 512 × 1024; the two calls 7.67), at
    [8, 16384, 64] (10.58, 10.96; 14.41) and, for the two calls above the
    budget, at [8, 16384, 128] (13.25 against 13.98). A tile beyond
    512 × 1024 needs more than the default 16 MiB of scoped VMEM, which
    is why every backward call states ``_BWD_VMEM_BYTES``. ``block_q`` is
    the FALLBACK tile for non-divisible t (the caller's forward/padding
    granule), not an override of the tuned table."""
    if t % 1024 == 0:
        return 1024, 1024
    if t % 512 == 0:
        return 512, 512
    if pallas and t % 256 == 0:
        return 256, 256
    bq = block_q or 128
    return bq, bq


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_core(q, k, v, mask, causal, scale, block_q, interpret):
    out, _ = _core_fwd(q, k, v, mask, causal, scale, block_q, interpret)
    return out


def _core_fwd(q, k, v, mask, causal, scale, block_q, interpret):
    b, t, h, d = q.shape
    s = _resolve_scale(scale, d)
    to_btd = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    out, lse = _flash_fwd_btd(to_btd(q), to_btd(k), to_btd(v), mask,
                              n_heads=h, scale=s, causal=causal,
                              block_q=block_q or 128, interpret=interpret,
                              auto_tile=block_q is None)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3), lse


def _core_fwd_rule(q, k, v, mask, causal, scale, block_q, interpret):
    out, lse = _core_fwd(q, k, v, mask, causal, scale, block_q, interpret)
    return out, (q, k, v, mask, out, lse)


def _core_bwd_rule(causal, scale, block_q, interpret, res, g):
    import os
    q, k, v, mask, out, lse = res
    b, t, h, d = q.shape
    s = _resolve_scale(scale, d)
    to_btd = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    use_jax = os.environ.get("DL4JTPU_FLASH_BWD") == "jax"
    bq_bwd, bk_bwd = _bwd_tiles(t, block_q, pallas=not use_jax)
    if use_jax:
        # JAX-blockwise fallback (same math, lax.scan tiles)
        mk = jnp.repeat(mask.astype(jnp.float32), h, axis=0)
        dq, dk, dv = _flash_bwd_btd(
            to_btd(q), to_btd(k), to_btd(v), mk, to_btd(out), lse,
            to_btd(g), scale=s, causal=causal, block_q=bq_bwd,
            block_k=bk_bwd)
    else:
        # tile choice: see _bwd_tiles (the PERF.md sweep rationale)
        dq, dk, dv = _flash_bwd_btd_pallas(
            to_btd(q), to_btd(k), to_btd(v), mask, to_btd(out), lse,
            to_btd(g), scale=s, causal=causal, block_q=bq_bwd,
            block_k=bk_bwd, interpret=interpret, n_heads=h)
    back = lambda a: a.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return back(dq), back(dk), back(dv), jnp.zeros_like(mask,
                                                        dtype=jnp.float32)


_flash_core.defvjp(_core_fwd_rule, _core_bwd_rule)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    interpret=None, mask=None):
    """[b, t, h, d] attention with Pallas forward and backward kernels
    (``DL4JTPU_FLASH_BWD=jax`` selects the lax.scan blockwise backward
    instead). t must divide by ``block_q`` (default: auto — 128-row
    granularity, upgraded to wider tiles when t and the VMEM budget allow;
    an explicit ``block_q`` is used as-is). ``mask``: optional [b, t_kv]
    key-validity mask (1=attend); rows with no attendable keys output 0.
    ``interpret``: None = what ``util.xla.kernel_mode`` says at trace
    time — compiled for the TPU unless a caller asked for interpret mode
    (``util.xla.interpret_kernels``). Off the TPU with no such request
    the call fails in the Pallas lowering; it never quietly interprets."""
    if interpret is None:
        from ..util.xla import kernel_mode
        interpret = kernel_mode() == "interpret"
    if mask is None:
        mask = jnp.ones((q.shape[0], q.shape[1]), jnp.float32)
    return _flash_core(q, k, v, jnp.asarray(mask, jnp.float32), causal,
                       scale, block_q, interpret)


def flash_available(q_shape, mask, block_q: int = 128) -> bool:
    """Should the Pallas path serve this call?

    Only where a kernel can run at all (``util.xla.kernel_mode``: on the
    TPU backend, or in interpret mode when a caller asked for it — any
    other backend takes the XLA path whatever the flag says). Then
    ``DL4JTPU_FLASH_ATTENTION``: ``1`` forces it on, ``0`` off; unset =
    auto — on for t ≥ 4096 on the TPU backend (where it measures ≥2× over
    the XLA path on v5e; below that XLA's fusion already sits at the
    memory floor). Non-multiple-of-block lengths always use the XLA path.

    NOTE: this runs at *trace* time. The chosen route is baked into any
    already-compiled jit — set the flag before the first trace of a step
    function (or clear jit caches via ``fn.clear_cache()`` /
    ``jax.clear_caches()``) for a toggle to take effect."""
    import os
    from ..util.xla import kernel_mode
    flag = os.environ.get("DL4JTPU_FLASH_ATTENTION", "auto")
    mode = kernel_mode()
    if flag == "0" or mode is None or q_shape[1] % block_q:
        return False
    if mask is not None and getattr(mask, "shape", None) is not None \
            and tuple(mask.shape) != (q_shape[0], q_shape[1]):
        return False   # only [b, t_kv] key masks map onto the kernel
    if flag == "1":
        return True
    return q_shape[1] >= 4096 and mode == "mosaic"
