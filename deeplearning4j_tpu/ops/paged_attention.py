"""Paged KV-cache attention primitives (scatter, chunked gather, pure XLA).

vLLM-style block cache (PAPERS: PagedAttention/SOSP'23) for the
continuous-batching decode path: per-layer K/V live in preallocated
``[num_pages, page_size, heads * head_dim]`` block pools; each sequence
owns an ordered *page table* of physical page ids. A decode step scatters
the new tokens' K/V into the pools at (page, offset) and then reads them
back through :func:`paged_read_attention`, which walks the page table a
chunk of pages at a time and keeps a running softmax. The walk stops at
the furthest live position of the dispatch (``max(rel_pos) + t_new``,
read on the device), so the empty tail of the window is never gathered.

What holds against the dense streaming cache
(``SelfAttentionLayer._apply_streaming``): the same keys take part
(every key the causal window admits, and no other), in the same dtypes
(logits, max-subtracted exp and sums in float32, the pools' own dtype
for K/V), with the same conventions for a fully masked row (``m_safe``,
the ``1e-30`` floor). The float32 sums are formed chunk by chunk rather
than over the whole window at once, so logits differ from the dense
path by rounding, not bit for bit; GREEDY TOKENS are equal on the
parity suite in ``tests/test_decode.py`` for sequences within the
window (past the window the paths evict at different granularity — a
page here, a token there — and diverge by design).

Layout conventions (shared with ``serving/kv_cache.py`` and
``serving/decode.py``):

- a pool is stored ``[num_pages, page_size, h*d]``: a token's heads lie
  side by side in one row. A decode program's entry parameter has the
  default layout, and with a row of ``h*d`` (a whole number of 128-lane
  tiles) that is the layout the compiler keeps for the scatter and the
  gather; stored ``[..., h, d]`` with ``d`` = 64 minor, it relaid every
  donated pool at the program's entry and again before its result (96
  pool-sized copies a dispatch at 24 layers; PERF.md, PR 32).
- a LATENT attention vertex (``nn/conf/mla``) owns one pool, ``[num_pages,
  page_size, row]``: a token's key row, whose first columns are also its
  value (:func:`paged_read_attention`, ``v_width``). The row is rounded up
  to a whole number of 128-lane tiles with zeros (576 numbers in 640
  columns): at 576 the compiler stored the pool pages-minor and relaid it
  at every program's entry and before its result (TPU compiler, PR 35).
- NO PROGRAM RESHAPES A WHOLE POOL (nor transposes, converts or does
  arithmetic on one): a pool enters a scatter and a gather and leaves
  as the scatter's result. Heads are split off the GATHERED pages only,
  turned so that the keys lie on the minor axis (:func:`paged_gather`:
  ``[S, keys, h*d]`` → ``[S, h, d, keys]``); a reshape of the pool
  itself, even back and forth as a "view", brings the copies back
  twofold. ``tests/test_pool_layout.py`` holds every program to it.
- THE ONE EXCEPTION: a read over ONE K/V head (the latent read, a
  one-head or one-K/V-head layer) has no heads to split, so its chunk
  is folded as gathered, ``[S, keys, row]`` (:func:`paged_gather_rows`):
  the logits contract the row axis, ``p·v`` the keys axis, and the
  accumulator is ``[S, rows, d_v]``. The turn exists for heads of 64,
  which split off the minor axis only by a relayout of every chunk;
  over one head it is pure cost: at the latent cell's shapes the TPU
  compiler turned every trip's chunk in float32 (``f32[32,512,640]
  copy``) and relaid the accumulator after the loop (PERF.md, the
  paged read). The choice is the read's own static shape, ``h == 1``.
- That rule is about the jaxpr. THE COMPILED TEXT broke it until PR 38:
  with a bfloat16 query over a float32 pool both products of the read
  are float32 matrix products at default precision, which the MXU feeds
  with bfloat16 operands; the TPU compiler's bfloat16 propagation saw
  that the chunk is needed only to bfloat16, walked back through the
  select, the reshape and the gather (which pass values through
  unchanged) and put the rounding on the whole pool, before the read
  loop: a pool-sized ``convert`` a layer in every such program (a sixth
  of the latent cell's device time; PERF.md, PR 38). So the read rounds
  the chunk ITSELF, right after the gather
  (``jax.lax.reduce_precision`` to the query's exponent and mantissa
  bits, the dtype unchanged), exactly where the products would round
  (:func:`read_rounds_chunk`); the products see the very numbers they
  saw, and the compiler has nothing left to push onto the pool. An
  ``optimization_barrier`` on the chunk, a bitcast of the chunk to
  ``uint32`` and back, and a gather from a ``uint32`` view of the pool
  do NOT hold it (PR 35, PR 38).
- page tables are ``[lanes, pages_per_seq]`` int32 of PHYSICAL page ids;
  unallocated entries hold the SENTINEL ``num_pages`` (one past the pool)
  — gathers fill zeros there, scatters drop.
- write positions are VIEW-relative slots ``global_pos - base`` where
  ``base`` is the number of evicted positions (pages_evicted ×
  page_size); ``-1`` marks padded lanes/tokens (dropped).
- sliding-window overflow is PAGE EVICTION, done host-side by the engine
  (the page table shifts, ``base`` advances) — positions stay global, and
  the causal mask below automatically hides a recycled page's stale tail.

Every SHAPE is static (tables stay ``[lanes, pages_per_seq]``, a trip
of the read takes :func:`read_trip_tokens` keys, a rule on the read's
static shapes), so the scheduler admits and retires sequences every step
without retracing; only the read loop's trip count is data
(:func:`read_trip_count`, a ``while`` on the device).

THE TRIP FOLLOWS THE VALUE WIDTH. Each trip rescales the whole float32
accumulator (rows x ``d_v``) and forms the trip's logits (rows x keys):
with the latent read's 512 value columns over 128-key trips the rescale
moved four times the logits' bytes, three quarters of what a prefill
read moved (PERF.md, PR 40). A trip takes the largest power-of-two
multiple of :data:`READ_CHUNK_TOKENS` keys not above ``d_v``, so the
rescale is no larger than the logits; the products, the keys and the
precision are the same. A trip whose float32 logits would pass
:data:`READ_TRIP_LOGIT_BYTES` is halved (the latent cell's 32-lane
prefill chunk: 256 keys). OPT, the grouped-query read and EVA (``d_v``
64 and 128) take 128, as before.

Each primitive runs under a ``jax.named_scope`` (``attn.paged_write``,
``attn.paged_gather``, ``attn.paged_softmax``): metadata only, so that a
device trace can tell the paged read from the rest of a decode step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["paged_write", "paged_gather", "paged_gather_rows",
           "paged_read_attention",
           "read_trip_tokens", "read_chunk_pages", "read_trip_count",
           "read_rounds_chunk", "eva_read_attention", "eva_read_trips",
           "READ_CHUNK_TOKENS", "READ_TRIP_LOGIT_BYTES"]

# tokens of K/V one trip of the read loop gathers at the least (a whole
# number of pages; the whole table where the window is shorter)
READ_CHUNK_TOKENS = 128
# the float32 logits one trip may hold before its keys are halved
READ_TRIP_LOGIT_BYTES = 512 << 20


def _write_targets(num_pages, page_size, page_table, write_slots):
    """``(phys, off)`` of each new token, ``[S, t_new]`` both: padded
    tokens (slot < 0) and sentinel table entries land out of bounds, so
    a scatter with ``mode="drop"`` discards them."""
    p_idx = jnp.clip(write_slots // page_size, 0, page_table.shape[1] - 1)
    phys = jnp.take_along_axis(page_table, p_idx, axis=1)
    return (jnp.where(write_slots >= 0, phys, num_pages),
            write_slots % page_size)


@jax.named_scope("attn.paged_write")
def paged_write(pool, new, page_table, write_slots):
    """Scatter new K (or V) rows into the block pool.

    pool: ``[num_pages, page_size, h*d]`` — or, int8-quantized, a
    ``(q_int8, scales)`` tuple (see :func:`_paged_write_q8`); new:
    ``[S, t_new, h, d]``, written as rows of ``h*d``; page_table:
    ``[S, P]`` physical page ids; write_slots: ``[S, t_new]``
    view-relative slot per token (``-1`` = padded, dropped). Returns the
    updated pool (same structure as the input). Out-of-range/sentinel
    targets are dropped, so padded lanes can never corrupt a live page.
    """
    if isinstance(pool, tuple):
        return _paged_write_q8(pool, new, page_table, write_slots)
    phys, off = _write_targets(pool.shape[0], pool.shape[1], page_table,
                               write_slots)
    rows = new.reshape(new.shape[0], new.shape[1], -1)
    return pool.at[phys, off].set(rows.astype(pool.dtype), mode="drop")


def _paged_write_q8(pool, new, page_table, write_slots):
    """int8 write path: ``pool = (q, scales)`` with ``q`` the
    ``[num_pages, page_size, h*d]`` int8 codes and ``scales`` the
    per-(page, head) ``[num_pages, h]`` f32 quantization step.

    Scales are MONOTONE per page: a write first folds the new rows'
    amax into ``new_scale = max(old_scale, amax/127)``, rescales the
    touched pages' existing codes by ``old/new`` (duplicate page ids
    scatter identical values, so the update is idempotent), then writes
    the new rows quantized at the new scale. Monotonicity keeps already
    written tokens valid without tracking per-row scales; the bounded
    requantization drift it costs is covered by the int8 quality gate
    (logit max-err + greedy divergence, see PERF.md). The per-head
    rescale works on the GATHERED pages (split into heads there), never
    on the pool.
    """
    q, scales = pool
    num_pages, page_size = q.shape[0], q.shape[1]
    s, t, h, d = new.shape
    phys, off = _write_targets(num_pages, page_size, page_table,
                               write_slots)                   # [S, t]
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
    pages_q = pages_q.reshape(-1, page_size, h, d).astype(jnp.float32)
    q = q.at[flat_phys].set(
        jnp.round(pages_q * r).astype(jnp.int8).reshape(-1, page_size,
                                                        h * d),
        mode="drop")
    # 3) quantize the new rows at the new step and scatter them in
    s_tok = jnp.take(new_scales, phys, axis=0,
                     mode="fill", fill_value=0.0)              # [S, t, h]
    rows = jnp.round(newf / jnp.maximum(s_tok[..., None], 1e-30))
    rows = jnp.clip(rows, -127, 127).astype(jnp.int8)
    q = q.at[phys, off].set(rows.reshape(s, t, h * d), mode="drop")
    return (q, new_scales)


def read_rounds_chunk(q_dtype, pool_dtype, rows: int) -> bool:
    """Whether the read's products round their K/V operand to the
    query's dtype, so that :func:`paged_read_attention` rounds the chunk
    it gathered: the query is a float narrower than the pool's, and the
    products are matrix products, ``rows`` > 1 query rows a K/V head
    (``t_new`` times the grouped query heads). With one row the TPU
    compiler forms both products on the vector unit in float32 and uses
    K/V unrounded, and so does the read. ``pool_dtype`` is None for an
    int8 pool (dequantized to float32 inside the gather, never rounded).
    The engine's ``decode_kv_chunk_rounded_tokens_total`` asks here too.
    """
    if pool_dtype is None or rows <= 1:
        return False
    q_dtype, pool_dtype = jnp.dtype(q_dtype), jnp.dtype(pool_dtype)
    return (jnp.issubdtype(q_dtype, jnp.floating)
            and jnp.issubdtype(pool_dtype, jnp.floating)
            and q_dtype.itemsize < pool_dtype.itemsize)


@jax.named_scope("attn.paged_gather")
def paged_gather(pool, page_table, heads, round_to=None):
    """Gather pages into a contiguous view with the KEYS ON THE MINOR
    AXIS: the read loop's gather, one chunk of each lane's table at a
    time.

    pool: ``[num_pages, page_size, h*d]`` (or the int8
    ``(q, scales)`` tuple — dequantized here, the one place reads
    happen); page_table: ``[S, P]`` → ``[S, h, d, P·page_size]`` with
    ``h = heads``. The GATHERED pages are turned (``[S, keys, h*d]`` →
    ``[S, h*d, keys]``) and the heads split off the second-minor axis,
    which costs nothing; the pool is not touched. Splitting them off the
    minor axis instead (``h*d`` → ``h, d`` with ``d`` = 64, half a lane
    tile) made the compiler pad and relay every chunk, a fifth of a
    decode block's device time (PERF.md, PR 32). Sentinel entries read
    as zeros (masked by the causal window in
    :func:`paged_read_attention` anyway). The read of several K/V heads
    takes this form; a read over one K/V head takes
    :func:`paged_gather_rows`.

    ``round_to`` (a float dtype; an array pool only) rounds the gathered
    pages to that dtype's exponent and mantissa bits and keeps the
    pool's dtype: the rounding the read's products would do, done on the
    chunk before the reshape and the turn, so that the compiler does not
    do it on the pool (module docstring).
    """
    codes = pool[0] if isinstance(pool, tuple) else pool
    g = _take_pages(codes, page_table, round_to)
    s, p, page_size, f = g.shape
    g = jnp.swapaxes(g.reshape(s, p * page_size, f), 1, 2)
    g = g.reshape(s, heads, f // heads, p, page_size)
    if isinstance(pool, tuple):
        sc = jnp.take(pool[1], page_table, axis=0,
                      mode="fill", fill_value=0.0)            # [S, P, h]
        g = g.astype(jnp.float32) * jnp.swapaxes(sc, 1, 2)[:, :, None, :,
                                                            None]
    return g.reshape(s, heads, f // heads, p * page_size)


def _take_pages(codes, page_table, round_to):
    """``[S, P, page_size, row]``: the pages of ``page_table`` (sentinels
    as zeros), rounded to ``round_to``'s bits where it is given."""
    g = jnp.take(codes, page_table, axis=0, mode="fill", fill_value=0)
    if round_to is not None:
        to = jnp.finfo(round_to)
        g = jax.lax.reduce_precision(g, exponent_bits=to.nexp,
                                     mantissa_bits=to.nmant)
    return g


@jax.named_scope("attn.paged_gather")
def paged_gather_rows(pool, page_table, round_to=None):
    """Gather pages AS THEY ARE STORED, one key row after another: the
    read loop's gather where the pool holds one K/V head (module
    docstring, the one exception). ``pool``, ``page_table`` and
    ``round_to`` as for :func:`paged_gather`; returns ``[S, P·page_size,
    row]``, no turn, so the products contract the row and the keys
    where they lie. An int8 pool's codes are dequantized by their page's
    one scale."""
    codes = pool[0] if isinstance(pool, tuple) else pool
    g = _take_pages(codes, page_table, round_to)
    s, p, page_size, f = g.shape
    if isinstance(pool, tuple):
        sc = jnp.take(pool[1], page_table, axis=0,
                      mode="fill", fill_value=0.0)            # [S, P, 1]
        g = g.astype(jnp.float32) * sc[:, :, None, :]
    return g.reshape(s, p * page_size, f)


def read_trip_tokens(d_v: int = READ_CHUNK_TOKENS,
                     logit_rows: int = 1) -> int:
    """Keys one trip of a read whose value is ``d_v`` wide takes: the
    largest power-of-two multiple of :data:`READ_CHUNK_TOKENS` not above
    ``d_v`` (at least :data:`READ_CHUNK_TOKENS`), so that the rescale of
    the accumulator, rows x ``d_v`` a trip, is no larger than the trip's
    logits, rows x keys; and no more keys than keep the trip's float32
    logits, ``logit_rows`` (lanes x K/V heads x query rows a head) x keys
    x 4 B, within :data:`READ_TRIP_LOGIT_BYTES`."""
    trip = READ_CHUNK_TOKENS
    while (2 * trip <= d_v
           and logit_rows * 2 * trip * 4 <= READ_TRIP_LOGIT_BYTES):
        trip *= 2
    return trip


def read_chunk_pages(page_size: int, pages_per_seq: int,
                     d_v: int = READ_CHUNK_TOKENS,
                     logit_rows: int = 1) -> int:
    """Pages one trip of the read loop gathers (:func:`read_trip_tokens`
    keys, the whole table where it is shorter)."""
    return min(max(1, read_trip_tokens(d_v, logit_rows) // page_size),
               pages_per_seq)


def read_trip_count(rel_pos, t_new: int, page_size: int,
                    pages_per_seq: int, xp=jnp,
                    d_v: int = READ_CHUNK_TOKENS, logit_rows: int = 1):
    """Chunks the read of one dispatch visits: up to the furthest live
    position ``max(rel_pos) + t_new`` over its lanes, clamped to the
    table, in trips of :func:`read_chunk_pages` for ``d_v`` and
    ``logit_rows``. ``xp`` is ``jnp`` inside the program and ``numpy``
    where the engine counts the same number on the host."""
    cp = read_chunk_pages(page_size, pages_per_seq, d_v, logit_rows)
    chunk = cp * page_size
    n_chunks = -(-pages_per_seq // cp)
    return xp.minimum((xp.max(rel_pos) + t_new + chunk - 1) // chunk,
                      n_chunks)


# every layer of a program reads at the same shapes: traced once
@functools.partial(jax.jit, static_argnames=("group", "v_width"))
def paged_read_attention(q, k_pool, v_pool, page_table, rel_pos, scale,
                         group: int = 1, v_width=None):
    """Causal attention of new queries over each lane's paged window,
    read only as far as the furthest live position of the dispatch.

    The streaming-decode softmax of
    ``SelfAttentionLayer._apply_streaming`` (max-subtraction in f32,
    masked exp, 1e-30 denominator floor) as a running softmax over
    chunks of :func:`read_chunk_pages` pages (as many keys as the value
    is wide, :func:`read_trip_tokens`): trip ``c`` gathers
    ``page_table[:, c·cp:(c+1)·cp]`` of K and of V, masks that chunk's
    logits by ``key_idx <= rel_pos + query offset`` and folds them into
    the running max ``m``, sum ``l`` and accumulator. The trip count is
    :func:`read_trip_count`, so the loop is a ``while`` and the chunks
    past the furthest lane are never gathered; a key the mask hid
    contributed exactly 0 before and is simply not read now.

    q: ``[S, t_new, h, d]`` (compute dtype); k_pool/v_pool:
    ``[num_pages, page_size, h*d]`` (or int8 ``(q, scales)`` tuples);
    page_table: ``[S, P]``; rel_pos: ``[S]`` view-relative position of
    each lane's FIRST new query (``global_pos - base``). Returns
    ``[S, t_new, h, d]``.

    ``group`` > 1 is grouped-query attention as its caller lays it out
    (``SelfAttentionLayer.apply_paged``): ``h`` counts the K/V heads and
    the query axis holds ``group`` query heads for each of the
    ``t_new // group`` positions, position-major, so query row ``j`` sits
    at position ``rel_pos + j // group``. The pools' rows are ``h*d`` wide
    either way.

    ``v_width`` (with ``v_pool`` None) is the read over a LATENT pool
    (``nn/conf/mla.MLAttentionLayer``): one pool holds a token's key row
    and the value is that row's first ``v_width`` columns, so a chunk of
    pages is gathered once and sliced; returns ``[S, t_new, h, v_width]``.

    Where the products round K/V to ``q``'s dtype
    (:func:`read_rounds_chunk`: a bfloat16 query over float32 pools, more
    than one query row a K/V head), each gathered chunk is rounded so,
    in the pools' dtype, before it is turned: the same numbers reach the
    products, and no rounding is left for the compiler to place on a
    whole pool.

    The form follows ``h``: over several K/V heads each chunk is turned
    (:func:`paged_gather`, :func:`_fold_chunk`); over ONE (the latent
    read, a one-head layer) it is folded as gathered
    (:func:`paged_gather_rows`, :func:`_fold_rows`: ``[S, keys, row]``,
    the accumulator ``[S, rows, d_v]``), with the same keys, trips,
    rounding, precision and softmax bookkeeping.
    """
    codes = k_pool[0] if isinstance(k_pool, tuple) else k_pool
    num_pages, page_size = codes.shape[0], codes.shape[1]
    kv_dtype = jnp.float32 if isinstance(k_pool, tuple) else codes.dtype
    out_dtype = jnp.result_type(q.dtype, kv_dtype)
    s, t_new, h, d = q.shape
    round_to = q.dtype if read_rounds_chunk(
        q.dtype, None if isinstance(k_pool, tuple) else codes.dtype,
        t_new) else None
    d_v = d if v_width is None else v_width
    pages_per_seq = page_table.shape[1]
    cp = read_chunk_pages(page_size, pages_per_seq, d_v, s * h * t_new)
    pad = -pages_per_seq % cp
    if pad:     # a last, partial chunk reads sentinel pages: zeros, masked
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)),
                             constant_values=num_pages)
    trips = read_trip_count(rel_pos, t_new // group, page_size,
                            pages_per_seq, d_v=d_v,
                            logit_rows=s * h * t_new)
    if group == 1:
        q_idx = rel_pos[:, None] + jnp.arange(t_new)[None, :]  # [S, t_new]
    else:
        q_idx = rel_pos[:, None] + (jnp.arange(t_new) // group)[None, :]

    def allow(key_idx):
        return key_idx[None, None, :] <= q_idx[:, :, None]

    def chunk_table(c):
        return jax.lax.dynamic_slice_in_dim(page_table, c * cp, cp, axis=1)

    if h == 1:
        q_rows = q.reshape(s, t_new, d)

        def fold_rows(c, carry):
            table_c = chunk_table(c)
            k_c = paged_gather_rows(k_pool, table_c, round_to)  # [S, keys, row]
            v_c = (paged_gather_rows(v_pool, table_c, round_to)
                   if v_width is None else k_c[..., :v_width])
            return _fold_rows(carry, q_rows, k_c, v_c, c, scale, allow)

        carry = jax.lax.fori_loop(
            0, trips, fold_rows,
            _softmax_start((s, t_new), (s, t_new, d_v), out_dtype))
        return _softmax_end(carry, out_dtype).reshape(s, t_new, 1, d_v)

    def fold(c, carry):
        table_c = chunk_table(c)
        k_c = paged_gather(k_pool, table_c, h, round_to)      # [S, h, d, chunk]
        v_c = (paged_gather(v_pool, table_c, h, round_to)
               if v_width is None else k_c[:, :, :v_width])
        return _fold_chunk(carry, q, k_c, v_c, c, scale, allow)

    carry = jax.lax.fori_loop(
        0, trips, fold,
        _softmax_start((s, h, t_new), (s, t_new, h, d_v), out_dtype))
    return _softmax_end(carry, out_dtype, turned=True)


def _softmax_start(rows, acc_shape, out_dtype):
    """The running softmax's carry before any key: max and sum ``[*rows,
    1]``, accumulator ``acc_shape``."""
    return (jnp.full((*rows, 1), -jnp.inf, jnp.float32),
            jnp.zeros((*rows, 1), jnp.float32),
            jnp.zeros(acc_shape, jnp.promote_types(out_dtype, jnp.float32)))


def _softmax_step(m, l, logits):
    """The bookkeeping of one chunk's masked float32 ``logits`` (keys on
    the last axis) in the running softmax, whatever the layout: the new
    max, the sum rescaled by the max's move, the chunk's max-subtracted
    exp ``p`` and the rescale ``alpha`` the caller applies to its
    accumulator."""
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.where(jnp.isneginf(logits), 0.0,
                  jnp.exp(logits - m_safe))
    alpha = jnp.exp(m - m_safe)       # 0 while m is still -inf
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    return m_new, l, p, alpha


def _fold_chunk(carry, q, k_c, v_c, c, scale, allow):
    """Fold one TURNED chunk (``k_c [S, h, d, keys]``, ``v_c [S, h, d_v,
    keys]``, keys on the minor axis: the read over several K/V heads, and
    EVA's) into the running softmax of ``q [S, t_new, h, d]``: logits in
    float32, max-subtracted exp, the sum and the accumulator ``[S, t_new,
    h, d_v]`` rescaled by the max's move. The chunk is trip ``c`` of its
    table, and ``allow(key_idx) [S, t_new, keys]`` says which of its keys
    (numbered by their place in the table) each query row takes."""
    m, l, acc = carry
    with jax.named_scope("attn.paged_softmax"):
        logits = jnp.einsum("bqhd,bhdk->bhqk", q, k_c) * scale
        chunk = k_c.shape[-1]
        key_idx = c * chunk + jnp.arange(chunk)
        logits = jnp.where(allow(key_idx)[:, None],
                           logits.astype(jnp.float32), -jnp.inf)
        m_new, l, p, alpha = _softmax_step(m, l, logits)
        pv = jnp.einsum("bhqk,bhdk->bqhd", p.astype(q.dtype), v_c)
        acc = jnp.swapaxes(alpha, 1, 2) * acc + pv.astype(acc.dtype)
    return m_new, l, acc


def _fold_rows(carry, q, k_c, v_c, c, scale, allow):
    """Fold one chunk AS GATHERED (``k_c [S, keys, row]``, ``v_c [S, keys,
    d_v]``: the read over one K/V head) into the running softmax of ``q
    [S, rows, row]``: the logits contract the row axis, ``p·v`` the keys
    axis, and the accumulator is ``[S, rows, d_v]``, so nothing is turned
    before the products or after the loop. ``c`` and ``allow`` as for
    :func:`_fold_chunk`."""
    m, l, acc = carry
    with jax.named_scope("attn.paged_softmax"):
        logits = jnp.einsum("bqd,bkd->bqk", q, k_c) * scale
        chunk = k_c.shape[1]
        key_idx = c * chunk + jnp.arange(chunk)
        logits = jnp.where(allow(key_idx), logits.astype(jnp.float32),
                           -jnp.inf)
        m_new, l, p, alpha = _softmax_step(m, l, logits)
        pv = jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), v_c)
        acc = alpha * acc + pv.astype(acc.dtype)
    return m_new, l, acc


def _softmax_end(carry, out_dtype, turned=False):
    """The running softmax's output: the accumulator over the sum (floored
    at 1e-30, so a row that took no key reads 0); ``turned``: the sum is
    ``[S, h, t_new, 1]`` beside an accumulator ``[S, t_new, h, d_v]``."""
    _, l, acc = carry
    with jax.named_scope("attn.paged_softmax"):
        l = jnp.maximum(l, 1e-30)
        out = acc / (jnp.swapaxes(l, 1, 2) if turned else l)
        return out.astype(out_dtype)


def eva_read_trips(rel_pos, positions, t_new: int, window: int,
                   chunk_size: int, page_size: int, window_pages: int,
                   summary_pages: int, xp=jnp, d_v: int = READ_CHUNK_TOKENS,
                   logit_rows: int = 1):
    """The bounds of the EVA read of one dispatch (:func:`eva_read_attention`),
    in trips of :func:`read_chunk_pages` pages (for ``d_v`` and
    ``logit_rows``): ``(first, end)`` over the
    window pages, from the chunk of the earliest window start any lane's
    first query has to the furthest live position, and the trips over the
    summary pages, up to the most summary rows any query sees. ``rel_pos``
    view-relative (slot 0 is the lane's window start), ``positions``
    absolute, both ``[S]``; ``xp`` as for :func:`read_trip_count`, so that
    the engine counts on the host what the program reads."""
    chunk = read_chunk_pages(page_size, window_pages, d_v,
                             logit_rows) * page_size
    start = positions // window * window - (positions - rel_pos)
    seen = xp.max((positions + t_new - 1) // window * window // chunk_size)
    cs = read_chunk_pages(page_size, summary_pages, d_v, logit_rows)
    return (xp.min(start) // chunk,
            read_trip_count(rel_pos, t_new, page_size, window_pages, xp,
                            d_v, logit_rows),
            xp.minimum((seen + cs * page_size - 1) // (cs * page_size),
                       -(-summary_pages // cs)))


@functools.partial(jax.jit, static_argnames=("window", "chunk_size"))
def eva_read_attention(q, k_pool, v_pool, page_table, rel_pos, positions,
                       scale, window: int, chunk_size: int):
    """EVA attention of new queries (``nn/conf/eva``) over each lane's
    paged window and its summary rows, merged in ONE running softmax.

    ``page_table [S, P]``: the window's ``window / page_size + 1`` pages
    (slot 0 the window's first position, so ``rel_pos`` indexes it as the
    plain read's view does), then the summary pages, ``page_size``
    summaries a page (summary ``c`` of chunk ``c``: row ``c % page_size`` of
    summary page ``c // page_size``), in the same K and V pools. Query ``j``
    of a lane, at absolute position ``t = positions + j``, takes the window
    rows from its own window's start ``window * floor(t / window)`` to
    ``t`` and the summary rows ``c < window * floor(t / window) /
    chunk_size``; nothing else is allowed into its softmax. The summary
    part is read first (scope ``eva.read_summary``), the window part after
    (``eva.read_window``), each a ``while`` over chunks of
    :func:`read_chunk_pages` pages bounded by :func:`eva_read_trips`; the
    window part starts at the chunk of the earliest window start, and a
    lane's pages before its first query's window start read as holes, so
    no lane gathers a page of a window it has left (the one fused block
    that crosses an edge still holds the last window's pages for its first
    steps). Logits, exp and sums in float32; K/V rounded per chunk as
    :func:`paged_read_attention` does. Returns ``[S, t_new, h, d]``."""
    num_pages, page_size = k_pool.shape[0], k_pool.shape[1]
    s, t_new, h, d = q.shape
    out_dtype = jnp.result_type(q.dtype, k_pool.dtype)
    round_to = q.dtype if read_rounds_chunk(q.dtype, k_pool.dtype,
                                            t_new) else None
    win = window // page_size + 1
    q_abs = positions[:, None] + jnp.arange(t_new)              # [S, t_new]
    start = q_abs // window * window
    seen = start // chunk_size                  # summary rows a query sees
    start_rel = start - (positions - rel_pos)[:, None]
    q_rel = rel_pos[:, None] + jnp.arange(t_new)
    first, end, trips = eva_read_trips(
        rel_pos, positions, t_new, window, chunk_size, page_size, win,
        page_table.shape[1] - win, d_v=d, logit_rows=s * h * t_new)
    own = jnp.where(jnp.arange(win)[None, :] * page_size < start_rel[:, :1],
                    num_pages, page_table[:, :win])

    def loop(table, lo, hi, allow):
        cp = read_chunk_pages(page_size, table.shape[1], d, s * h * t_new)
        pad = -table.shape[1] % cp
        if pad:     # a last, partial chunk reads sentinel pages: zeros, masked
            table = jnp.pad(table, ((0, 0), (0, pad)),
                            constant_values=num_pages)

        def fold(c, carry):
            table_c = jax.lax.dynamic_slice_in_dim(table, c * cp, cp, axis=1)
            return _fold_chunk(carry, q, paged_gather(k_pool, table_c, h,
                                                      round_to),
                               paged_gather(v_pool, table_c, h, round_to),
                               c, scale, allow)
        return lambda carry: jax.lax.fori_loop(lo, hi, fold, carry)

    carry = _softmax_start((s, h, t_new), (s, t_new, h, d), out_dtype)
    with jax.named_scope("eva.read_summary"):
        carry = loop(page_table[:, win:], 0, trips,
                     lambda key: key[None, None, :] < seen[:, :, None])(carry)
    with jax.named_scope("eva.read_window"):
        carry = loop(own, first, end,
                     lambda key: (key[None, None, :] >= start_rel[:, :, None])
                     & (key[None, None, :] <= q_rel[:, :, None]))(carry)
    return _softmax_end(carry, out_dtype, turned=True)
