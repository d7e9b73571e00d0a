"""Paged KV-cache attention primitives (gather/scatter, pure XLA).

vLLM-style block cache (PAPERS: PagedAttention/SOSP'23) for the
continuous-batching decode path: per-layer K/V live in preallocated
``[num_pages, page_size, heads, head_dim]`` block pools; each sequence
owns an ordered *page table* of physical page ids. A decode step scatters
the new tokens' K/V into the pools at (page, offset) and gathers each
sequence's pages back into a contiguous ``[window, heads, head_dim]``
view — the gathered view IS the dense streaming cache reassembled, so the
attention math here mirrors ``SelfAttentionLayer._apply_streaming`` term
for term and greedy decode through the arena is bit-exact against the
dense full-cache path for sequences within the window (the parity suite
in ``tests/test_decode.py`` pins it; past the window the paths evict at
different granularity — a page here, a token there — and diverge by
design).

Layout conventions (shared with ``serving/kv_cache.py`` and
``serving/decode.py``):

- page tables are ``[lanes, pages_per_seq]`` int32 of PHYSICAL page ids;
  unallocated entries hold the SENTINEL ``num_pages`` (one past the pool)
  — gathers fill zeros there, scatters drop.
- write positions are VIEW-relative slots ``global_pos - base`` where
  ``base`` is the number of evicted positions (pages_evicted ×
  page_size); ``-1`` marks padded lanes/tokens (dropped).
- sliding-window overflow is PAGE EVICTION, done host-side by the engine
  (the page table shifts, ``base`` advances) — positions stay global, and
  the causal mask below automatically hides a recycled page's stale tail.

Everything is plain gather/scatter + einsum: XLA lowers it well on both
the CPU test mesh and TPU, and there is no dynamic shape anywhere — the
scheduler can admit/retire sequences every step without retracing.

Each primitive runs under a ``jax.named_scope`` (``attn.paged_write``,
``attn.paged_gather``, ``attn.paged_softmax``): metadata only, so that a
device trace can tell the paged read from the rest of a decode step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["paged_write", "paged_gather", "paged_attention"]


@jax.named_scope("attn.paged_write")
def paged_write(pool, new, page_table, write_slots):
    """Scatter new K (or V) rows into the block pool.

    pool: ``[num_pages, page_size, h, d]`` — or, int8-quantized, a
    ``(q_int8, scales)`` tuple (see :func:`_paged_write_q8`); new:
    ``[S, t_new, h, d]``; page_table: ``[S, P]`` physical page ids;
    write_slots: ``[S, t_new]`` view-relative slot per token (``-1`` =
    padded, dropped). Returns the updated pool (same structure as the
    input). Out-of-range/sentinel targets are dropped, so padded lanes
    can never corrupt a live page.
    """
    if isinstance(pool, tuple):
        return _paged_write_q8(pool, new, page_table, write_slots)
    num_pages, page_size = pool.shape[0], pool.shape[1]
    p_idx = jnp.clip(write_slots // page_size, 0, page_table.shape[1] - 1)
    off = write_slots % page_size
    phys = jnp.take_along_axis(page_table, p_idx, axis=1)
    # padded tokens (slot < 0) and sentinel table entries both land out of
    # bounds → mode="drop" discards the write
    phys = jnp.where(write_slots >= 0, phys, num_pages)
    return pool.at[phys, off].set(new.astype(pool.dtype), mode="drop")


def _paged_write_q8(pool, new, page_table, write_slots):
    """int8 write path: ``pool = (q, scales)`` with ``q`` the
    ``[num_pages, page_size, h, d]`` int8 codes and ``scales`` the
    per-(page, head) ``[num_pages, h]`` f32 quantization step.

    Scales are MONOTONE per page: a write first folds the new rows'
    amax into ``new_scale = max(old_scale, amax/127)``, rescales the
    touched pages' existing codes by ``old/new`` (duplicate page ids
    scatter identical values, so the update is idempotent), then writes
    the new rows quantized at the new scale. Monotonicity keeps already
    written tokens valid without tracking per-row scales; the bounded
    requantization drift it costs is covered by the int8 quality gate
    (logit max-err + greedy divergence, see PERF.md).
    """
    q, scales = pool
    num_pages, page_size = q.shape[0], q.shape[1]
    h = q.shape[2]
    p_idx = jnp.clip(write_slots // page_size, 0, page_table.shape[1] - 1)
    off = write_slots % page_size
    phys = jnp.take_along_axis(page_table, p_idx, axis=1)
    phys = jnp.where(write_slots >= 0, phys, num_pages)       # [S, t]
    newf = new.astype(jnp.float32)
    # 1) fold the new rows' amax into the touched pages' scales
    amax_tok = jnp.max(jnp.abs(newf), axis=-1)                # [S, t, h]
    flat_phys = phys.reshape(-1)
    amax_page = (jnp.zeros((num_pages, h), jnp.float32)
                 .at[flat_phys].max(amax_tok.reshape(-1, h), mode="drop"))
    new_scales = jnp.maximum(scales, amax_page / 127.0)
    # 2) rescale ONLY the touched pages' existing codes to the new step
    ratio = jnp.where(new_scales > 0, scales / new_scales, 0.0)
    pages_q = jnp.take(q, flat_phys, axis=0, mode="fill", fill_value=0)
    r = jnp.take(ratio, flat_phys, axis=0,
                 mode="fill", fill_value=0.0)[:, None, :, None]
    q = q.at[flat_phys].set(
        jnp.round(pages_q.astype(jnp.float32) * r).astype(jnp.int8),
        mode="drop")
    # 3) quantize the new rows at the new step and scatter them in
    s_tok = jnp.take(new_scales, phys, axis=0,
                     mode="fill", fill_value=0.0)              # [S, t, h]
    rows = jnp.round(newf / jnp.maximum(s_tok[..., None], 1e-30))
    rows = jnp.clip(rows, -127, 127).astype(jnp.int8)
    q = q.at[phys, off].set(rows, mode="drop")
    return (q, new_scales)


@jax.named_scope("attn.paged_gather")
def paged_gather(pool, page_table):
    """Gather each lane's pages into a contiguous view.

    pool: ``[num_pages, page_size, h, d]`` (or the int8
    ``(q, scales)`` tuple — dequantized here, the one place reads
    happen); page_table: ``[S, P]`` → ``[S, P·page_size, h, d]``.
    Sentinel entries read as zeros (masked by the causal window in
    :func:`paged_attention` anyway).
    """
    if isinstance(pool, tuple):
        q, scales = pool
        g = jnp.take(q, page_table, axis=0, mode="fill", fill_value=0)
        sc = jnp.take(scales, page_table, axis=0,
                      mode="fill", fill_value=0.0)            # [S, P, h]
        g = g.astype(jnp.float32) * sc[:, :, None, :, None]
        s, p, page_size, h, d = g.shape
        return g.reshape(s, p * page_size, h, d)
    g = jnp.take(pool, page_table, axis=0, mode="fill", fill_value=0)
    s, p, page_size, h, d = g.shape
    return g.reshape(s, p * page_size, h, d)


@jax.named_scope("attn.paged_softmax")
def paged_attention(q, k_view, v_view, rel_pos, scale):
    """Causal attention of new queries over the gathered paged view.

    The EXACT streaming-decode softmax math from
    ``SelfAttentionLayer._apply_streaming`` (max-subtraction in f32,
    masked exp, 1e-30 denominator floor) — kept identical on purpose so
    the paged path is bit-exact against the dense cache.

    q: ``[S, t_new, h, d]`` (compute dtype); k_view/v_view:
    ``[S, W, h, d]`` (cache dtype); rel_pos: ``[S]`` view-relative
    position of each lane's FIRST new query (``global_pos - base``).
    Returns ``[S, t_new, h, d]``.
    """
    t_new = q.shape[1]
    w = k_view.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_view) * scale
    key_idx = jnp.arange(w)
    q_idx = rel_pos[:, None] + jnp.arange(t_new)[None, :]     # [S, t_new]
    allow = key_idx[None, None, :] <= q_idx[:, :, None]       # [S, t_new, W]
    logits = jnp.where(allow[:, None], logits.astype(jnp.float32),
                       -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(jnp.isneginf(logits), 0.0, jnp.exp(logits - m_safe))
    weights = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(q.dtype), v_view)
