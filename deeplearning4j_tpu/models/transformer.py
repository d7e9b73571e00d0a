"""Decoder-only transformer LM as a ComputationGraphConfiguration.

No reference analog (the reference is LSTM-era); this is the long-context
model family built from the framework's own DSL pieces: pre-norm blocks of
``SelfAttentionLayer`` + time-distributed FFN with ``ElementWiseVertex``
residual adds, trained like any other ComputationGraph (one jitted step,
works with remat, and the attention op auto-routes to the Pallas flash
kernel at long sequence lengths — see ops/flash_attention.py).

TPU-native layout: every vertex is time-axis-preserving ([b, t, f] end to
end — ``TimeDistributedDenseLayer`` einsums keep the time dim, no
flatten/rebuild reshapes), so under a sequence-sharded mesh
(``parallel.sequence.SequenceParallelGraphTrainer``) every op partitions
trivially over the time axis and attention rides the ring — no reshape of
a sharded dim, no gather.

Two input contracts:
  - default: one-hot [b, t, vocab] inputs + one-hot labels (``mcxent``) —
    fine for toy vocabularies and the existing parallel-trainer tests;
  - ``input_ids=True``: integer token ids [b, t] through an
    ``EmbeddingSequenceLayer`` gather, integer labels through
    ``sparse_mcxent`` — the REALISTIC-vocab path (a one-hot [b, t, V]
    host tensor at V ≫ 8 cannot survive; ids are 4 bytes/token however
    large V grows). Same math: one-hot @ W ≡ W[ids].
"""

from __future__ import annotations

from typing import List, Optional

import jax
import numpy as np

from ..nn.conf.attention import SelfAttentionLayer
from ..nn.conf.kda import KimiDeltaAttention
from ..nn.conf.builders import NeuralNetConfiguration
from ..nn.conf.graph import ElementWiseVertex
from ..nn.conf.inputs import InputType
from ..nn.conf.layers import (EmbeddingSequenceLayer, LayerNormalization,
                              RnnOutputLayer)
from ..nn.conf import ssm as _ssm
from ..nn.conf.recurrent import TimeDistributedDenseLayer


def transformer_lm(vocab_size: int, *, n_layers: int = 4,
                   d_model: int = 256, n_heads: int = 4, d_ff: int = 1024,
                   updater: str = "adam", learning_rate: float = 3e-4,
                   seed: int = 42, dtype: str = "float32",
                   moe_experts: int = 0, moe_top_k: int = 2,
                   input_ids: bool = False,
                   max_cache_t: Optional[int] = None):
    """Causal LM: in-proj → n_layers × [ln → attention (+res) → ln → ffn
    (+res)] → final ln → vocab head.

    ``moe_experts > 0`` replaces every block's dense FFN with a top-k
    routed ``MoELayer`` (d_hidden=d_ff per expert, load-balancing aux loss
    included in training) — the expert-parallel model family; shard the
    expert dim over an ``ep`` mesh axis via
    ``parallel.expert.ExpertParallelGraphTrainer``.

    ``input_ids=True`` switches to the integer-id contract (see module
    docstring): feed [b, t] int32 ids, label with [b, t] int32 ids.

    ``max_cache_t`` arms every block's attention with a streaming K/V
    cache of that many positions — required for autoregressive decode
    (:func:`generate` / the paged serving engine); overflowing it slides
    the attention window (see ``SelfAttentionLayer.cache_overflow``)."""
    if d_model % n_heads:
        raise ValueError(f"d_model={d_model} not divisible by "
                         f"n_heads={n_heads}")
    from ..nn.conf.moe import MoELayer
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater(updater).learning_rate(learning_rate)
          .dtype(dtype)
          .graph_builder()
          .add_inputs("in"))
    if input_ids:
        gb.add_layer("embed",
                     EmbeddingSequenceLayer(n_in=vocab_size,
                                            n_out=d_model,
                                            activation="identity"), "in")
    else:
        gb.add_layer("embed",
                     TimeDistributedDenseLayer(n_in=vocab_size,
                                               n_out=d_model,
                                               activation="identity"), "in")
    prev = "embed"
    for i in range(n_layers):
        b = f"blk{i}"
        gb.add_layer(f"{b}_ln1", LayerNormalization(), prev)
        gb.add_layer(f"{b}_attn",
                     SelfAttentionLayer(n_in=d_model, n_out=d_model,
                                        n_heads=n_heads, causal=True,
                                        max_cache_t=max_cache_t),
                     f"{b}_ln1")
        gb.add_vertex(f"{b}_res1", ElementWiseVertex(op="add"),
                      prev, f"{b}_attn")
        gb.add_layer(f"{b}_ln2", LayerNormalization(), f"{b}_res1")
        if moe_experts > 0:
            gb.add_layer(f"{b}_moe",
                         MoELayer(n_in=d_model, n_out=d_model,
                                  d_hidden=d_ff, n_experts=moe_experts,
                                  top_k=moe_top_k),
                         f"{b}_ln2")
            ff_out = f"{b}_moe"
        else:
            gb.add_layer(f"{b}_ff1",
                         TimeDistributedDenseLayer(n_in=d_model,
                                                   n_out=d_ff,
                                                   activation="relu"),
                         f"{b}_ln2")
            gb.add_layer(f"{b}_ff2",
                         TimeDistributedDenseLayer(n_in=d_ff,
                                                   n_out=d_model,
                                                   activation="identity"),
                         f"{b}_ff1")
            ff_out = f"{b}_ff2"
        gb.add_vertex(f"{b}_res2", ElementWiseVertex(op="add"),
                      f"{b}_res1", ff_out)
        prev = f"{b}_res2"
    gb.add_layer("final_ln", LayerNormalization(), prev)
    gb.add_layer("out", RnnOutputLayer(
        n_in=d_model, n_out=vocab_size, activation="softmax",
        loss="sparse_mcxent" if input_ids else "mcxent"), "final_ln")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(1 if input_ids else vocab_size))
    return gb.build()


# --------------------------------------------------------------------------
# autoregressive decode
# --------------------------------------------------------------------------


def stateful_vertices(net, wants: Optional[str] = None) -> List[str]:
    """Topo-ordered names of the vertices that own state during paged
    decode, one ordered list for the walker, the arena and the engine: the
    causal layers that declare ``paged_inputs``, the dispatch arrays their
    ``apply_paged`` takes after their state (a layer also declares its
    arena entry, ``paged_entry``, and its read, ``paged_read``).
    ``wants``: only those that take that array (``"lane_ids"``: a row of
    state a lane; ``"positions"``: absolute positions, a rotary term)."""
    layers = ((name, net._vertex_layer(name)) for name in net.topo_order)
    return [name for name, layer in layers
            if getattr(layer, "paged_inputs", ())
            and getattr(layer, "causal", True)
            and (wants is None or wants in layer.paged_inputs)]


def attention_vertices(net) -> List[str]:
    """Those of :func:`stateful_vertices` that read K/V through the lanes'
    page tables (a ``paged_read``): the layers that own a K/V cache (dense
    or paged) during decode."""
    return [name for name in stateful_vertices(net)
            if net._vertex_layer(name).paged_read is not None]


def kda_vertices(net) -> List[str]:
    """Names of the Kimi delta attention vertices (``nn/conf/kda``)."""
    return [name for name in stateful_vertices(net)
            if isinstance(net._vertex_layer(name), KimiDeltaAttention)]


def counting_vertices(net) -> List[str]:
    """Names of the expert layers that report their routing counts and
    take the dispatch's valid positions as their mask."""
    return [name for name in net.topo_order
            if getattr(net._vertex_layer(name), "wants_token_mask", False)]


def ids_embedding(net):
    """The ``EmbeddingSequenceLayer`` that takes the net's input (the
    integer-id input path), or None."""
    in_name = net.conf.network_inputs[0]
    for name in net.topo_order:
        layer = getattr(net.conf.vertices[name], "layer", None)
        if (in_name in net.conf.vertex_inputs[name]
                and isinstance(layer, EmbeddingSequenceLayer)):
            return layer
    return None


def filtered_probs_host(p: np.ndarray, temperature: float, top_k: int,
                        top_p: float) -> np.ndarray:
    """Host mirror of ``ops.sampling.filtered_probs`` for ONE row — the
    same temperature → top-k → renormalize → top-p → renormalize order,
    the same stable lower-id tie-breaking (documented in
    ``ops/sampling.py``; the host/device parity suite pins the pair)."""
    logits = np.log(np.maximum(p, 1e-30)) / float(temperature)
    logits -= logits.max()
    w = np.exp(logits)
    order = np.argsort(-w, kind="stable")
    if top_k and top_k > 0:
        w[order[int(top_k):]] = 0.0
    w /= max(w.sum(), 1e-30)
    if 0.0 < top_p < 1.0:
        w_desc = w[order]
        before = np.cumsum(w_desc) - w_desc
        w[order[before >= top_p]] = 0.0
        w /= max(w.sum(), 1e-30)
    return w


def sample_token(probs, temperature: float = 0.0, rng=None, *,
                 top_k: int = 0, top_p: float = 1.0) -> int:
    """Next-token choice from a softmax row — host-side, shared by the
    full-cache oracle (:func:`generate`) and the paged serving engine so
    the two paths CANNOT diverge in how they read the same distribution.
    ``temperature <= 0`` is greedy (argmax); otherwise an inverse-CDF
    draw (one uniform from ``rng``, a ``numpy.random.Generator``) over
    the temperature/top-k/top-p filtered distribution — the EXACT
    semantics of the on-device sampler ``ops.sampling.sample_tokens``
    (same filter order, same ascending-id inverse CDF), so host and
    device agree token-for-token at the same uniform."""
    p = np.asarray(probs, dtype=np.float64).reshape(-1)
    if temperature <= 0.0:
        return int(np.argmax(p))
    if rng is None:
        raise ValueError("temperature sampling needs an rng")
    w = filtered_probs_host(p, temperature, top_k, top_p)
    c = np.cumsum(w)
    gt = c > float(rng.random()) * c[-1]
    if gt.any():
        return int(np.argmax(gt))
    # u·total reached the top of the CDF (possible only through float
    # rounding): same last-positive-weight fallback as the device twin
    return int(np.max(np.nonzero(w > 0)[0]))


def generate(net, prompt_ids, max_new_tokens: int, *,
             temperature: float = 0.0, eos_id: Optional[int] = None,
             rng=None, top_k: int = 0, top_p: float = 1.0) -> np.ndarray:
    """Single-sequence full-cache autoregressive decode through the
    streaming ``rnn_time_step`` path — the offline API AND the parity
    oracle the continuous-batching serving engine is pinned bit-exact
    against (greedy; ``tests/test_decode.py``).

    The net must be an ids-mode ``transformer_lm`` built with
    ``max_cache_t`` set (the dense K/V window). Returns the generated ids
    as int32 (≤ ``max_new_tokens``; stops early at ``eos_id``, which is
    included in the output)."""
    from ..util.netutil import streaming_cache_limit
    limit = streaming_cache_limit(net)
    if limit is None:
        raise ValueError(
            "generate() needs streaming K/V caches — build the net with "
            "transformer_lm(..., max_cache_t=...)")
    prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
    if prompt.size < 1:
        raise ValueError("generate() needs a non-empty prompt")
    net.rnn_clear_previous_state()
    # the first window of the prompt goes in one chunk; any tail past
    # the window is fed token by token — eviction is chunk-granular
    # (the whole chunk's worth is evicted before its queries attend),
    # so single-token feeding is what gives every position the exact
    # (p - max_cache_t, p] sliding window
    first = min(len(prompt), limit)
    out = net.rnn_time_step(prompt[None, :first, None])
    for i in range(first, len(prompt)):
        out = net.rnn_time_step(prompt[None, i:i + 1, None])
    probs = np.asarray(out)[0, -1]
    toks: List[int] = []
    for i in range(int(max_new_tokens)):
        t = sample_token(probs, temperature, rng, top_k=top_k, top_p=top_p)
        toks.append(t)
        if (eos_id is not None and t == eos_id) \
                or i == int(max_new_tokens) - 1:
            break
        step = net.rnn_time_step(np.full((1, 1, 1), t, np.int32))
        probs = np.asarray(step)[0, -1]
    return np.asarray(toks, np.int32)


def oracle_stream_probs(net, token_ids) -> np.ndarray:
    """Per-position next-token distributions from the dense full-cache
    streaming path — the float32 quality oracle the int8 KV-page
    quantization gate compares against (``tests/test_prefix_cache.py``,
    ``bench.py``'s ``int8_logit_max_err``).

    Feeds ``token_ids`` through ``rnn_time_step`` with the same
    chunk-then-token schedule as :func:`generate` (first window in one
    chunk, tail token by token, so past-window positions see the exact
    sliding window) and returns ``[len(token_ids), V]`` float64 — row i
    is the model's distribution over the token FOLLOWING position i."""
    from ..util.netutil import streaming_cache_limit
    limit = streaming_cache_limit(net)
    if limit is None:
        raise ValueError(
            "oracle_stream_probs() needs streaming K/V caches — build "
            "the net with transformer_lm(..., max_cache_t=...)")
    ids = np.asarray(token_ids, np.int32).reshape(-1)
    if ids.size < 1:
        raise ValueError("oracle_stream_probs() needs at least one token")
    net.rnn_clear_previous_state()
    first = min(len(ids), limit)
    rows = [np.asarray(net.rnn_time_step(ids[None, :first, None]),
                       np.float64)[0]]
    for i in range(first, len(ids)):
        step = net.rnn_time_step(ids[None, i:i + 1, None])
        rows.append(np.asarray(step, np.float64)[0])
    return np.concatenate(rows, axis=0)


def paged_decode_forward(net, params, k_pools, v_pools, ids, page_tables,
                         write_slots, rel_pos, lane_ids=None, counts=None,
                         out_rows=None, positions=None, fed=None):
    """ONE traced forward of an ids-mode decoder graph in paged-decode
    mode: every stateful vertex (:func:`stateful_vertices`) reads and
    writes ITS entry of the two state lists, one state for a vertex of one
    pool (None in ``v_pools``) and two otherwise, through one call,
    ``layer.apply_paged(params, x, *state, *inputs, policy=...)``, whose
    ``inputs`` are the dispatch's arrays its ``paged_inputs`` name:
    ``tables`` (``page_tables``), ``write_slots``, ``rel_pos``,
    ``positions``, ``lane_ids``, ``valid`` (below) and ``fresh`` (a lane
    at ``rel_pos`` 0). Every other vertex applies exactly as in
    ``output()``. Pure w.r.t. its arguments, so the serving engine jits it
    once per (lanes, chunk) bucket and admission/retirement only ever
    change array CONTENTS.

    ids: ``[S, t_new]`` int32 (padded lanes: any value — their writes are
    dropped and their outputs ignored); page_tables: ``[S, P]``;
    write_slots: ``[S, t_new]`` view-relative slots (-1 = dropped);
    rel_pos: ``[S]``; lane_ids: ``[S]`` (only a net with per-lane state
    needs them; a padded slot of the bucket holds an id past the last
    lane; None where the caller hands those vertices' entries in as the
    dispatch's own rows, ``Mamba2Mixer.apply_paged``).

    Two masks over the ``[S, t_new]`` positions. A position's WRITE IS
    KEPT where its write slot is not -1: a recurrent state advances over
    those. A position is COMPUTED where it is no padding: an expert layer
    routes, computes and counts those. They differ only under a prefix
    hit, which re-feeds covered positions with dropped writes (their K/V
    is resident) for their distribution: ``fed [S]`` then gives each lane's
    number of fed positions and "computed" is ``column < fed``; without it
    the two masks are one. ``positions [S]`` (a net with
    vertices that take them only): the absolute position of each lane's
    first new token, which is ``rel_pos`` plus what the lane's window has
    evicted. A lane at ``rel_pos`` 0 starts a sequence, so its
    recurrent state starts from zero inside this program (no dispatch of
    its own resets a lane). Returns ``(probs [S, t_new, V], k_pools,
    v_pools)``; ``counts``, a list, receives the sum of the expert layers'
    routing counts (``nn.conf.moe.MOE_STATS``, int32) where the net has
    layers that count.

    out_rows: ``[S]`` int32, the one position of each lane whose
    distribution the caller will read (the serving engine: the last
    prompt token of the chunk). The output vertex then sees that row of
    its input alone, so the head's product and its softmax have ``S`` rows
    and ``probs`` is ``[S, V]``; every vertex before it still runs over
    all ``t_new`` positions (they write K/V, carry state, count routed
    pairs). ``None``: every position, as a verify pass needs.
    """
    import jax.numpy as jnp

    owners = stateful_vertices(net)
    if len(owners) != len(k_pools):
        raise ValueError(
            f"{len(k_pools)} pools for {len(owners)} stateful vertices")
    pool_ix = {n: i for i, n in enumerate(owners)}
    k_pools, v_pools = list(k_pools), list(v_pools)
    acts = {net.conf.network_inputs[0]: ids[:, :, None]}
    mbs = net._minibatch_map(ids.shape[0])
    # a position is valid where its write is kept: what a per-lane state
    # vertex advances over and an expert layer counts
    valid = (write_slots >= 0 if stateful_vertices(net, "valid")
             or counting_vertices(net) else None)
    computed = valid if fed is None else (
        jnp.arange(ids.shape[1], dtype=fed.dtype)[None, :] < fed[:, None])
    view = {"tables": page_tables, "write_slots": write_slots,
            "rel_pos": rel_pos, "positions": positions, "lane_ids": lane_ids,
            "valid": valid}
    stats = None
    head = net.conf.network_outputs[0]
    for name in net.topo_order:
        in_names = net.conf.vertex_inputs[name]
        layer = net._vertex_layer(name)
        i = pool_ix.get(name)
        if name == head and out_rows is not None:
            # the head at the wanted position only: [S, t_new, d] -> [S, 1, d]
            acts = dict(acts, **{n: jnp.take_along_axis(
                acts[n], out_rows[:, None, None], axis=1, mode="clip")
                for n in in_names})
        if i is not None:
            state = [p for p in (k_pools[i], v_pools[i]) if p is not None]
            out, *state = layer.apply_paged(
                params[name], acts[in_names[0]], *state,
                *(rel_pos == 0 if n == "fresh" else view[n]
                  for n in layer.paged_inputs), policy=net.policy)
            k_pools[i], v_pools[i] = (*state, None)[:2]
        elif getattr(layer, "wants_token_mask", False):
            out, st = net._apply_vertex(name, params[name], acts, {}, None,
                                        train=False, in_masks=[computed],
                                        minibatch=mbs[in_names[0]])
            stats = st["moe_stats"] if stats is None \
                else stats + st["moe_stats"]
        else:
            out, _ = net._apply_vertex(name, params[name], acts, {}, None,
                                       train=False,
                                       minibatch=mbs[in_names[0]])
        acts[name] = out
    if counts is not None and stats is not None:
        counts.append(stats)
    probs = acts[head]
    return (probs if out_rows is None else probs[:, 0, :]), k_pools, v_pools


# --------------------------------------------------------------------------
# fused multi-token decode + speculative draft/verify (traced bodies)
# --------------------------------------------------------------------------


def draft_transformer_lm(vocab_size: int, *, d_model: int = 128,
                         n_heads: int = 4, d_ff: int = 512,
                         seed: int = 42, dtype: str = "float32",
                         max_cache_t: Optional[int] = None):
    """The in-tree DRAFT model family for speculative decoding: a
    2-layer ids-mode :func:`transformer_lm` over the SAME vocabulary as
    the target it drafts for (same input contract, same softmax head, so
    its filtered distributions are directly comparable in the
    accept/reject step). Train it however the target was trained — the
    serving engine only requires matching vocab + window."""
    return transformer_lm(vocab_size, n_layers=2, d_model=d_model,
                          n_heads=n_heads, d_ff=d_ff, seed=seed,
                          dtype=dtype, input_ids=True,
                          max_cache_t=max_cache_t)


def fused_decode_loop(net, params, k_pools, v_pools, last_tokens,
                      page_tables, rel_pos, active, budget, eos_ids,
                      temperature, top_k, top_p, uniforms, lane_ids=None,
                      positions=None):
    """N decode steps over the paged arena in ONE dispatch — the
    device-resident inner loop the serving engine jits per lane bucket
    (``uniforms [S, N]`` fixes N at trace time). Each inner step
    writes the lane's pending token's K/V (paged scatter), runs one
    paged forward (t_new=1, identical math to the host-ticked step, so
    greedy output stays bit-exact vs :func:`generate`), samples the next
    token ON DEVICE (``ops.sampling.sample_tokens``: greedy argmax or
    temperature/top-k/top-p inverse-CDF at that step's uniform), and
    folds the EOS/budget self-retire mask: a finished lane keeps
    computing (fixed shapes) but its writes turn to ``-1`` slots —
    dropped by the scatter, same sentinel discipline as padded lanes —
    and its outputs are marked invalid.

    last_tokens ``[S]``: each lane's pending (sampled-but-unwritten)
    token; rel_pos ``[S]``: its view-relative slot (the host pre-draws /
    pre-rotates pages for the WHOLE block, so slots advance contiguously
    ``rel_pos .. rel_pos+N-1``); active ``[S]``: padded lanes start
    retired; budget ``[S]``: tokens this lane may still emit (≤ N);
    eos_ids ``[S]`` (-1 = none); temperature/top_k/top_p ``[S]``
    per-lane sampling config.

    Returns ``(tokens [S, N], valid [S, N], n_emitted [S], done [S],
    k_pools, v_pools)`` — ``valid`` is a prefix mask; ``n_emitted`` is
    both the number of valid tokens AND the number of K/V slots the lane
    actually wrote (the host advances its position by exactly this). A
    net with expert layers that count their routing returns the counts
    summed over the block's steps after ``done``
    (:func:`paged_decode_forward`), and the ``while`` carries them, as it
    carries a per-lane state vertex's state in the two state lists
    (``lane_ids``): a retired lane's dropped slot keeps its state still.
    ``positions [S]`` (a net with vertices that take them): each lane's
    absolute position at the block's first step; step ``i`` is at
    ``positions + i``.

    Two CPU-harness-measured costs shape the implementation: the loop
    is a ``while_loop`` (not ``scan``) so a block whose every lane
    self-retired stops computing instead of burning the remaining
    steps, and the filtered-sampling pipeline (two vocab argsorts per
    step) sits behind a ``lax.cond`` on "any lane sampling" — an
    all-greedy block (the common serving case) pays only the argmax."""
    import jax
    import jax.numpy as jnp

    from ..ops import sampling as _sampling

    n_steps = uniforms.shape[1]
    s = last_tokens.shape[0]
    any_sampled = jnp.any(temperature > 0)
    # a vertex's per-lane state: the block's lanes are the
    # same at every step, so their rows are gathered once here, carried
    # through the steps as they are, and scattered back once at the end
    k_pools, v_pools = list(k_pools), list(v_pools)
    owners = stateful_vertices(net)
    state_ix = [] if lane_ids is None else [
        owners.index(name) for name in stateful_vertices(net, "lane_ids")]
    whole = {i: (k_pools[i], v_pools[i]) for i in state_ix}
    for i in state_ix:
        k_pools[i], v_pools[i] = _ssm.gather_lanes(k_pools[i], v_pools[i],
                                                   lane_ids)

    def pick(row, u):
        return jax.lax.cond(
            any_sampled,
            lambda: _sampling.sample_tokens(row, temperature, top_k,
                                            top_p, u),
            lambda: jnp.argmax(row, axis=-1).astype(jnp.int32))

    def cond_fn(st):
        i, done = st[0], st[4]
        return (i < n_steps) & jnp.logical_not(jnp.all(done))

    def body_fn(st):
        i, k_pools, v_pools, cur, done, n_emitted, toks, valid, *stats = st
        slot = jnp.where(done, jnp.int32(-1), rel_pos + i)
        step_stats = []
        probs, k_pools, v_pools = paged_decode_forward(
            net, params, k_pools, v_pools, cur[:, None], page_tables,
            slot[:, None], rel_pos + i, None, step_stats,
            positions=None if positions is None else positions + i)
        with jax.named_scope("sample"):
            u = jax.lax.dynamic_index_in_dim(uniforms, i, axis=1,
                                             keepdims=False)
            tok = pick(probs[:, 0, :], u)
        emit = jnp.logical_not(done)
        n_emitted = n_emitted + emit.astype(jnp.int32)
        hit_eos = (eos_ids >= 0) & (tok == eos_ids)
        done = done | (emit & (hit_eos | (n_emitted >= budget)))
        cur = jnp.where(emit, tok, cur)
        toks = jax.lax.dynamic_update_index_in_dim(
            toks, jnp.where(emit, tok, -1), i, axis=1)
        valid = jax.lax.dynamic_update_index_in_dim(valid, emit, i,
                                                    axis=1)
        return (i + 1, k_pools, v_pools, cur, done, n_emitted, toks,
                valid, *(a + b for a, b in zip(stats, step_stats)))

    from ..nn.conf.moe import MOE_STATS
    counts = counting_vertices(net)
    st = (jnp.int32(0), k_pools, v_pools,
          last_tokens.astype(jnp.int32), jnp.logical_not(active),
          jnp.zeros(s, jnp.int32), jnp.full((s, n_steps), -1, jnp.int32),
          jnp.zeros((s, n_steps), bool),
          *([jnp.zeros(len(MOE_STATS), jnp.int32)] if counts else []))
    (_, k_pools, v_pools, _, done, n_emitted, toks, valid,
     *stats) = jax.lax.while_loop(cond_fn, body_fn, st)
    for i in state_ix:
        k_pools[i], v_pools[i] = _ssm.scatter_lanes(
            *whole[i], lane_ids, k_pools[i], v_pools[i])
    return (toks, valid, n_emitted, done, *stats, k_pools, v_pools)


def draft_decode_loop(net, params, k_pools, v_pools, last_tokens,
                      page_tables, rel_pos, active, write_budget,
                      temperature, top_k, top_p, uniforms):
    """The draft half of a speculative block: K+1 fused steps of the
    (small) draft net over ITS OWN pools through the SHARED page tables.
    ``uniforms`` is ``[S, K+1]``; the scan feeds
    ``[pending, d_1 .. d_K]`` — K+1 inputs — so the draft writes K/V for
    ALL of them, including ``d_K`` (whose output is discarded). That
    last write is what keeps the draft cache gap-free after a
    fully-accepted block: target and draft frontiers always advance in
    lockstep, and rejected tokens' stale K/V sits beyond the causal mask
    until legitimately overwritten (the same discipline as the fused
    loop's dropped writes).

    ``write_budget [S]`` caps each lane's writes at the tokens it can
    still legitimately emit: slots past ``rel_pos + write_budget - 1``
    are dropped. Without the cap a lane near its max-tokens (or near
    the window edge) would scatter up to K useless slots past its last
    possible position — forcing page draws (and, at the window edge,
    PREMATURE EVICTION that would break within-window bit-exactness)
    for tokens that can never exist. Draft outputs past the budget are
    garbage-in-garbage-out: the host truncates to the budget anyway,
    and every position the host can keep attends only to written slots.

    Returns ``(draft_tokens [S, K], draft_dists [S, K, V], k_pools,
    v_pools)`` — ``draft_dists`` are the FILTERED distributions the
    draft sampled from (what the accept/reject ratio needs); greedy
    lanes ignore them."""
    import jax
    import jax.numpy as jnp

    from ..ops import sampling as _sampling

    k1 = uniforms.shape[1]                  # K + 1
    any_sampled = jnp.any(temperature > 0)

    def body(carry, xs):
        k_pools, v_pools, cur = carry
        i, u = xs
        slot = jnp.where(active & (i < write_budget), rel_pos + i,
                         jnp.int32(-1))
        probs, k_pools, v_pools = paged_decode_forward(
            net, params, k_pools, v_pools, cur[:, None], page_tables,
            slot[:, None], rel_pos + i)
        row = probs[:, 0, :]
        greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
        # all-greedy batches skip the filter pipeline AND its dist
        # output (the verify greedy branch never reads it)
        dist, tok = jax.lax.cond(
            any_sampled,
            lambda: (lambda d: (d, jnp.where(
                temperature > 0, _sampling.inverse_cdf(d, u), greedy))
            )(_sampling.filtered_probs(row, temperature, top_k, top_p)),
            lambda: (jnp.zeros_like(row), greedy))
        return (k_pools, v_pools, tok), (tok, dist)

    init = (list(k_pools), list(v_pools), last_tokens.astype(jnp.int32))
    (k_pools, v_pools, _), (toks, dists) = jax.lax.scan(
        body, init, (jnp.arange(k1, dtype=jnp.int32), uniforms.T))
    return (toks[:k1 - 1].T, dists[:k1 - 1].transpose(1, 0, 2),
            k_pools, v_pools)


def spec_verify(net, params, k_pools, v_pools, last_tokens, page_tables,
                rel_pos, active, write_budget, draft_tokens, draft_dists,
                temperature, top_k, top_p, u_accept, u_fix):
    """The verify half of a speculative block: ONE batched target pass
    over ``[pending, d_1 .. d_K]`` (K+1 positions — the paged chunk
    forward is bit-exact vs feeding them one at a time, which is what
    makes greedy speculative output identical to target-only decode),
    then accept/reject + bonus selection ON DEVICE (Leviathan et al.):

    - greedy lanes (``temperature <= 0``): accept ``d_i`` iff it equals
      the target argmax at its position; the first mismatch position
      emits the target argmax instead; a fully-accepted block emits the
      position-K argmax as the BONUS token;
    - sampled lanes: accept ``d_i`` with probability
      ``min(1, q(d_i)/p(d_i))`` (filtered target / filtered draft) at
      ``u_accept[:, i]``; the first rejection samples from the residual
      ``max(q - p, 0)`` (fallback to ``q`` when the residual has no
      mass) at ``u_fix``; the bonus is a plain draw from the filtered
      position-K target distribution.

    Returns ``(emitted [S, K+1], valid [S, K+1], accepts [S], k_pools,
    v_pools)``: ``valid[:, j] = j <= accepts`` (a lane always emits its
    accepted prefix plus exactly one correction-or-bonus token); the
    HOST applies per-request EOS/max-tokens truncation to the valid
    prefix — each speculative block is one host tick anyway, so
    self-retire masking buys nothing here, unlike the fused loop.
    ``write_budget`` caps writes exactly as in
    :func:`draft_decode_loop` (same rationale, same slots)."""
    import jax
    import jax.numpy as jnp

    from ..ops import sampling as _sampling

    s, k = draft_tokens.shape
    k1 = k + 1
    ids = jnp.concatenate([last_tokens[:, None].astype(jnp.int32),
                           draft_tokens.astype(jnp.int32)], axis=1)
    offs = jnp.arange(k1, dtype=jnp.int32)[None, :]
    wslots = jnp.where(active[:, None] & (offs < write_budget[:, None]),
                       rel_pos[:, None] + offs, jnp.int32(-1))
    probs, k_pools, v_pools = paged_decode_forward(
        net, params, k_pools, v_pools, ids, page_tables, wslots, rel_pos)
    v = probs.shape[-1]
    t_hat = jnp.argmax(probs, axis=-1).astype(jnp.int32)      # [S, K+1]
    greedy = temperature <= 0
    acc_greedy = draft_tokens == t_hat[:, :k]

    def sampled_ops():
        rep = lambda a: jnp.repeat(a, k1, axis=0)             # noqa: E731
        q = _sampling.filtered_probs(probs.reshape(s * k1, v),
                                     rep(temperature), rep(top_k),
                                     rep(top_p)).reshape(s, k1, v)
        q_d = jnp.take_along_axis(q[:, :k, :], draft_tokens[:, :, None],
                                  axis=-1)[..., 0]            # [S, K]
        p_d = jnp.take_along_axis(draft_dists, draft_tokens[:, :, None],
                                  axis=-1)[..., 0]
        acc_sampled = u_accept < jnp.minimum(
            q_d / jnp.maximum(p_d, 1e-30), 1.0)
        resid = jnp.maximum(q[:, :k, :] - draft_dists, 0.0)
        has_mass = jnp.sum(resid, axis=-1, keepdims=True) > 0
        resid = jnp.where(has_mass, resid, q[:, :k, :])
        fix_dist = jnp.concatenate([resid, q[:, k:, :]],
                                   axis=1)                    # [S, K+1, V]
        fix_sampled = _sampling.inverse_cdf(
            fix_dist.reshape(s * k1, v),
            u_fix.reshape(s * k1)).reshape(s, k1)
        return (jnp.where(greedy[:, None], acc_greedy, acc_sampled),
                jnp.where(greedy[:, None], t_hat, fix_sampled))

    # all-greedy batches skip the filter/residual pipeline entirely
    accept, fix = jax.lax.cond(jnp.any(temperature > 0), sampled_ops,
                               lambda: (acc_greedy, t_hat))
    accepts = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                      axis=1)                                 # [S] 0..K
    fix_at_a = jnp.take_along_axis(fix, accepts[:, None], axis=1)
    d_pad = jnp.concatenate([draft_tokens,
                             jnp.zeros((s, 1), jnp.int32)], axis=1)
    j = jnp.arange(k1, dtype=jnp.int32)[None, :]
    emitted = jnp.where(j < accepts[:, None], d_pad,
                        jnp.where(j == accepts[:, None], fix_at_a, -1))
    valid = (j <= accepts[:, None]) & active[:, None]
    return emitted, valid, accepts, k_pools, v_pools
