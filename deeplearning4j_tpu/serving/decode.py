"""Continuous-batching autoregressive decode over a paged KV arena.

The generative-serving analog of the wave-batched ``/predict`` path
(PAPERS: Orca/OSDI'22 in-flight batching + vLLM/SOSP'23 paged KV):
instead of assembling a batch per request wave and holding every lane
until the LONGEST sequence finishes, a persistent decode loop admits new
sequences and retires finished ones (EOS / max-tokens / SLO deadline)
at EVERY decode step, against a fixed-lane token budget. K/V lives in
the shared :class:`~deeplearning4j_tpu.serving.kv_cache.PagedKVArena`,
so a retiring sequence's pages are reusable by the next admission at the
following step — the chip never idles on finished lanes and HBM never
holds worst-case caches for short sequences.

Two layers:

- :class:`PagedDecodeEngine` — owns the model, the arena, and the
  per-bucket jitted step (``models.transformer.paged_decode_forward``
  through ``util.xla.keyed_jit``). The scheduler packs working lanes
  into power-of-two batch buckets × two chunk lengths (1 for decode,
  ``prefill_chunk`` for prefill) — a FIXED trace set, so admission and
  retirement only ever change array contents and ``jit_retraces_total``
  stays pinned at 1 per bucket (tested), while a lone admission
  prefills at [1, C] cost instead of a full-width padded dispatch.
- :class:`DecodeScheduler` — the continuous-batching policy: bounded
  submit queue with shed-by-reason, page-reservation admission control,
  chunked prefill interleaved with decode, per-sequence deadlines,
  decode-aware ``drain()``, and a ``fence()`` that holds the loop at a
  step boundary (mid-decode model swaps are refused through it).

Three decode-step shapes:

- ``block_len=1`` (default): the host-ticked step — one dispatch,
  one host round-trip per generated token.
- ``block_len=N``: the FUSED loop — ``models.transformer.
  fused_decode_loop`` runs N decode steps (paged scatter, forward,
  on-device sampling, EOS/max-tokens self-retire mask) inside one
  ``lax.while_loop`` dispatch (early exit once every lane retires);
  the scheduler ticks once per block, so host
  bookkeeping amortizes N× and ``decode_host_syncs_total`` grows by 1
  per block instead of per token. N is bucketed to a power of two
  (``util.xla.pow2_bucket``, cap 64) so the trace ladder gains exactly
  one block-length axis.
- ``draft_net=``: SPECULATIVE decoding on top — a small draft model
  (same ``transformer_lm`` family, pools-only shadow arena indexed by
  the SAME page tables) drafts ``draft_k`` tokens per lane in one fused
  scan, the target verifies all of them in one batched K+1 chunk, and
  accept/reject + bonus selection happen on device (Leviathan et al.);
  a block emits 1..K+1 tokens for two dispatches and ONE host sync.

Greedy output through all three is bit-exact against the oracle (the
per-step math is identical; the verify chunk equals sequential feeding
the same way multi-chunk prefill does) — ``tests/test_fused_decode.py``
pins fused == ticked == oracle and speculative == target-only.

Greedy TOKENS through this path equal the single-sequence full-cache
oracle's (``models.transformer.generate``) for every sequence that stays
within the window (prompt + generated ≤ page_size × pages_per_seq): the
paged read visits the very keys the oracle's streaming cache holds, in
the same dtypes (its float32 sums are formed chunk by chunk, so logits
agree to rounding), and both paths share ``sample_token``.
``tests/test_decode.py`` pins it. PAST the window the two legitimately
diverge — the arena evicts a PAGE at a time while the oracle slides
token-by-token, so their attention windows differ by up to
``page_size - 1`` positions (both are valid sliding-window decodes;
size the window to the service's max context where exactness past it
matters).

Observability (same metrics plane as the wave path): shed-by-reason
rides ``serving_shed_total``; ``decode_batch_occupancy``,
``kv_pages_in_use``, ``decode_retired_total{reason}``, TTFT and
time-per-output-token histograms land in the scheduler's registry and
the ``/metrics`` exposition when wired into an ``InferenceServer``.
Every phase is timed by ``util.tracing.region`` and by nothing else:
``engine.enqueue`` / ``engine.device_wait`` / ``engine.fetch`` per
dispatch (``decode_dispatch_phase_seconds{kind, phase}``, with the bytes
fetched in ``decode_d2h_bytes_total{kind}``), ``sched.tick`` ⊃
``sched.retire_expired`` / ``sched.admit`` / ``sched.prefill`` /
``sched.decode`` per tick, ``sched.wait_idle`` / ``sched.wait_blocked``
in the loop (``decode_sched_wait_seconds{why}``); under a profiler
session they are host spans on the device trace's clock. How far the
paged read of each dispatch went is counted beside what its lanes'
whole windows hold: ``decode_kv_read_tokens_total{kind}`` over
``decode_kv_window_tokens_total{kind}``, each attention vertex with its
own trip (``ops.paged_attention.read_trip_tokens``), and the part of it
read in trips longer than 128 keys,
``decode_kv_long_trip_tokens_total{kind}``, and by vertices of one K/V
head, whose read folds each chunk as gathered,
``decode_kv_unturned_tokens_total{kind}``. What the Kimi delta attention
vertices compute and carry is counted the same way:
``kda_positions_total{form, kind}`` (chunked or step; a real lane's
written position or padding) and ``kda_state_bytes_total`` (the per-lane
state read and written, ``_note_kda``).

What the engine serves is read off the net's stateful vertices, each of
whose layers declares the dispatch arrays its ``apply_paged`` takes
(``paged_inputs``), what the arena holds for it (``paged_entry``) and the
shape of its read (``paged_read``): the engine names no kind of layer.
What a net makes it carry beside K/V pages is handed to the decode
programs as one more argument, so that a net without it keeps the
programs it had: the lanes' ids where a vertex keeps a row of state a lane
(``lane_ids``), each lane's ABSOLUTE position where a vertex takes it
(``positions``; a rotary term: the view-relative position indexes the
cache, the absolute one turns the keys), and under the prefix cache the
positions each lane fed (``fed``; an expert layer computes a re-fed
position whose write is dropped, and skips padding). The lanes' page
tables, and admission, paging and release against the allocator, are
``kv_cache.LaneTables``'s, which also keeps the one other kind of table
(an EVA lane's window and summary pages, ``nn/conf/eva``).

Fault seam: ``"serving.decode_step"`` before every prefill/decode
dispatch (chaos tests script outages at exact step boundaries).
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from collections import Counter, deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..models import transformer as _transformer
from ..nn.conf import moe as _moe
from ..ops import grouped_ffn as _ffn
from ..ops import paged_attention as _paged
from ..util import faults as _faults
from ..util import flightrecorder as _flight
from ..util import metrics as _metrics
from ..util import tracing as _tracing
from ..util import xla as _xla
from ..util.resilience import SYSTEM_CLOCK, Clock, Deadline
from ..util.tracing import region
from .kv_cache import LaneTables, PagedKVArena

__all__ = ["PagedDecodeEngine", "DecodeScheduler", "DecodeRequest",
           "SchedulerSaturated", "SchedulerDraining"]


class SchedulerSaturated(RuntimeError):
    """Submit refused: the bounded request queue is full (shed — the
    generative analog of the wave path's queue-full 503)."""

    def __init__(self, msg: str, retry_after: float = 1.0):
        super().__init__(msg)
        self.retry_after = float(retry_after)


class SchedulerDraining(RuntimeError):
    """Submit refused: the scheduler is draining or stopped."""


class DecodeRequest:
    """Handle for one generative request: the scheduler appends tokens as
    they are produced and signals ``event`` on finish. ``finish_reason``
    ∈ {eos, max_tokens, deadline, error, shutdown}.

    ``ttft_breakdown`` (stamped at the first token, when the scheduler
    has a clock that advances) decomposes the measured TTFT into
    components that sum to it (each also observed into
    ``decode_ttft_component_seconds``): ``queue_wait`` (submit → lane
    admission),
    ``prefill`` (this request's own prefill-dispatch wall, compile
    excluded), ``compile`` (fresh-trace compiles its prefill ticks
    paid — 0 after ``warmup()``), and ``dispatch`` (the remainder: the
    shared continuous-batching ticks' other dispatches + host
    bookkeeping between admission and the first token)."""

    __slots__ = ("prompt", "max_new_tokens", "temperature", "eos_id",
                 "deadline", "rng", "tokens", "finish_reason", "error",
                 "event", "t_submit", "t_admit", "t_first_token",
                 "t_done", "top_k", "top_p", "span", "ttft_breakdown",
                 "prefix_covered_tokens", "blocked_by", "deliveries")

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 temperature: float, eos_id: Optional[int],
                 deadline: Deadline, rng, t_submit: float,
                 top_k: int = 0, top_p: float = 1.0):
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.deadline = deadline
        self.rng = rng
        self.tokens: List[int] = []
        self.finish_reason: Optional[str] = None
        self.error: Optional[str] = None
        self.event = threading.Event()
        self.t_submit = t_submit
        self.t_admit: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.t_done: Optional[float] = None
        self.span = None            # request-root tracing span
        self.ttft_breakdown: Optional[Dict[str, float]] = None
        # prompt tokens covered by a prefix-cache hit at admission
        # (0 = miss or caching disabled) — stamped by the scheduler
        self.prefix_covered_tokens = 0
        # why the last admission pass that ran while this request was
        # queued stopped short: "lanes", "pages", or "none" if no pass
        # was refused between its submit and its admission
        self.blocked_by = "none"
        # (scheduler-clock instant, tokens handed over) per delivery: a
        # fused block hands up to block_len tokens at once
        self.deliveries: List[Tuple[float, int]] = []

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    @property
    def retryable(self) -> bool:
        """True when the request died with the ENGINE (pools rebuilt
        after a dispatch failure, server shutting down) rather than on
        its own terms — safe to replay elsewhere because no terminal
        answer was produced and any partial ``tokens`` are preserved.
        This is the contract a fleet router's idempotent replay rides."""
        return self.finish_reason in ("error", "shutdown")

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the request finishes (True) or ``timeout`` real
        seconds pass (False)."""
        return self.event.wait(timeout)


# sequence states inside the scheduler
_PREFILL, _DECODE = "prefill", "decode"

# a dispatch and its parts span 0.1 ms (an enqueue) to 1 s (a fused block)
_TICK_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                 0.025, 0.05, 0.1, 0.25, 1.0)


def _module_name(ladder_key: str) -> str:
    """``paged_decode[S8xT128xP128]`` → ``paged_decode_S8_T128_P128``:
    the trace-ladder key as a function name, so each decode program's
    XLA module (the profiler's ``XLA Modules`` line) carries its ladder
    rung instead of twelve ``jit_step``."""
    head, _, dims = ladder_key.partition("[")
    return "_".join([head, *dims.rstrip("]").split("x")])


class _Sequence:
    __slots__ = ("req", "lane", "state", "cursor", "last_token",
                 "prefill_s", "compile_s", "covered")

    def __init__(self, req: DecodeRequest, lane: int):
        self.req = req
        self.lane = lane
        self.state = _PREFILL
        self.cursor = 0              # prompt tokens already prefilled
        self.last_token = 0          # next token to feed in decode
        self.prefill_s = 0.0         # own prefill dispatch wall (TTFT)
        self.compile_s = 0.0         # compile wall its ticks paid
        self.covered = 0             # positions below this are cache-hit
        #                              (their K/V is resident: fed tokens
        #                              there re-attend but never write)


class PagedDecodeEngine:
    """Model + arena + the per-bucket jitted paged step function.

    ``max_batch`` is the lane count (the decode token budget per step);
    ``page_size × pages_per_seq`` is each lane's attention window (longer
    sequences slide by page eviction); ``num_pages`` defaults to the
    worst case ``max_batch × pages_per_seq`` (no overcommit) — size it
    smaller to let the scheduler queue admissions on page pressure.
    """

    def __init__(self, net, *, max_batch: int = 8, page_size: int = 16,
                 pages_per_seq: int = 8, num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 registry: Optional[_metrics.MetricsRegistry] = None,
                 block_len: int = 1, draft_net=None, draft_k: int = 4,
                 prefix_cache: bool = False,
                 kv_dtype: Optional[str] = None):
        self._validate_net(net)
        self.net = net
        self.lanes = int(max_batch)
        self.page_size = int(page_size)
        self.pages_per_seq = int(pages_per_seq)
        self.window = self.page_size * self.pages_per_seq
        if num_pages is None:
            num_pages = self.lanes * self.pages_per_seq
        if self.pages_per_seq > num_pages:
            raise ValueError(
                f"pages_per_seq={self.pages_per_seq} exceeds the arena "
                f"({num_pages} pages) — one sequence could never run")
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk \
            else min(16, self.window)
        if not (1 <= self.prefill_chunk <= self.window):
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be in "
                f"[1, window={self.window}]")
        # fused-block length: bucketed to a power of two (cap 64) so the
        # trace ladder's block axis is a FIXED set however callers
        # configure it; 1 = the host-ticked step
        self.block_len = _xla.pow2_bucket(int(block_len), cap=64)
        if self.block_len > self.window:
            raise ValueError(
                f"block_len={self.block_len} exceeds the window "
                f"({self.window}) — a block must fit the lane's view")
        self.registry = registry if registry is not None \
            else _metrics.MetricsRegistry()
        self._check_decode_config(net)
        # what the stateful vertices declare: a row of state a lane,
        # absolute positions (a rotary term), a lane's table of one window
        # and its summary pages (EVA); and the expert layers whose routing
        # is counted
        self.state_layers = _transformer.stateful_vertices(net, "lane_ids")
        self.position_layers = _transformer.stateful_vertices(
            net, "positions")
        self.eva_layers = [n for n, e in self._entries(net).items()
                           if e.window]
        self.kda_layers = _transformer.kda_vertices(net)
        experts = [net._vertex_layer(v)
                   for v in _transformer.counting_vertices(net)]
        self._counting_layers = len(experts)
        self._counting = bool(experts)
        # the expert kernel and the routing shape its grid is sized by,
        # where every expert layer shares them
        grids = {(e.kernel_name, e.top_k, e.held, e.n_experts)
                 for e in experts}
        self._moe_grid = grids.pop() if len(grids) == 1 else None
        self._refuse_unsupported(net, prefix_cache=bool(prefix_cache),
                                 draft_net=draft_net, kv_dtype=kv_dtype)
        # what a decode program takes after its other arguments, by what
        # the net needs (none for a net that needs none: its programs keep
        # the arguments they always had): the lanes' ids (recurrent state
        # is a row a lane), their absolute positions, and, in a paged
        # dispatch under the prefix cache, how many positions each lane
        # fed (an expert layer computes a re-fed position whose write is
        # dropped, and skips padding)
        self._extra = (("lane_ids",) * bool(self.state_layers)
                       + ("positions",) * bool(self.position_layers))
        self._extra_paged = self._extra + ("fed",) * bool(
            self._counting and prefix_cache)
        self.vocab = int(_transformer.ids_embedding(net).n_in)
        self.draft_net = draft_net
        self.draft_k = int(draft_k)
        if draft_net is not None:
            if int(block_len) != 1:
                raise ValueError(
                    "block_len and draft_net are mutually exclusive — "
                    "speculative blocks are draft_k-sized; configure one "
                    "decode-step shape")
            if not (1 <= self.draft_k <= 16):
                raise ValueError(
                    f"draft_k={self.draft_k} out of range [1, 16]")
            if self.draft_k + 1 > self.window:
                raise ValueError(
                    f"draft_k={self.draft_k}+1 exceeds the window "
                    f"({self.window})")
            self._validate_net(draft_net)
            self._check_decode_config(draft_net)
            draft_vocab = int(_transformer.ids_embedding(draft_net).n_in)
            if draft_vocab != self.vocab:
                raise ValueError(
                    f"draft vocab {draft_vocab} != "
                    f"target vocab {self.vocab} — accept/reject compares "
                    "distributions over one vocabulary")
        with _xla.startup_region("startup.engine_build", self.registry):
            self._build_arenas(net, int(num_pages), bool(prefix_cache),
                               kv_dtype)
        # the lanes' page tables and what fills them; the free lanes here
        self.lane_tables = LaneTables(
            self.arena, self.lanes, self.pages_per_seq,
            prefill_chunk=self.prefill_chunk, block_len=self.block_len,
            registry=self.registry, shadow=self.draft_arena)
        self.table_pages = self.lane_tables.width
        self._free_lanes = deque(range(self.lanes))
        self._jit_cache: Dict[str, object] = {}
        # prefix-cache observability (the allocator owns the page-level
        # gauge/histogram; admission-level outcomes live here)
        self._m_prefix_hits = self.registry.counter(
            "kv_prefix_hits_total",
            "Prefix-cache admission outcomes: full (whole prompt "
            "resident), partial (some full-page prefix resident), miss",
            ("result",))
        self._m_prefix_pages = self.registry.counter(
            "kv_prefix_hit_pages_total",
            "KV pages mapped from the prefix cache instead of prefilled")
        # host-round-trip accounting (the satellite the fused loop is
        # measured by): every dispatch that synchronizes the host bumps
        # the sync counter and lands in the "dispatch" component of the
        # tick histogram; the scheduler observes the remainder of its
        # tick as "bookkeeping"
        self._m_syncs = self.registry.counter(
            "decode_host_syncs_total",
            "Decode dispatches whose results the host synchronized on")
        self._m_dispatches = self.registry.counter(
            "decode_dispatches_total",
            "Device dispatches issued by the decode engine", ("kind",))
        self._m_tick = self.registry.histogram(
            "decode_host_tick_seconds",
            "Scheduler tick wall split into dispatch (device compute + "
            "sync) vs host bookkeeping components", ("component",),
            buckets=_TICK_BUCKETS)
        self._m_phase = self.registry.histogram(
            "decode_dispatch_phase_seconds",
            "One dispatch split where it happens: enqueue (the jitted "
            "call returns), device_wait (block_until_ready of its "
            "outputs), fetch (device->host copy of them); the three sum "
            "to decode_host_tick_seconds{component=dispatch}",
            ("kind", "phase"),
            buckets=_TICK_BUCKETS)
        self._m_d2h = self.registry.counter(
            "decode_d2h_bytes_total",
            "Bytes of dispatch outputs copied device->host", ("kind",))
        self._m_kv_read = self.registry.counter(
            "decode_kv_read_tokens_total",
            "Key positions the paged read of the dispatches visited: "
            "lanes of the bucket x the chunks up to the furthest live "
            "position x chunk tokens, for every step of a block",
            ("kind",))
        self._m_kv_window = self.registry.counter(
            "decode_kv_window_tokens_total",
            "Key positions of the same dispatches' whole windows: lanes "
            "of the bucket x window, for every step of a block",
            ("kind",))
        self._m_kv_rounded = self.registry.counter(
            "decode_kv_chunk_rounded_tokens_total",
            "The part of decode_kv_read_tokens_total read by dispatches "
            "whose paged read rounds each gathered chunk to the query's "
            "dtype (ops.paged_attention.read_rounds_chunk: a query "
            "narrower than the pools, more than one query row a K/V "
            "head)", ("kind",))
        self._m_kv_long_trip = self.registry.counter(
            "decode_kv_long_trip_tokens_total",
            "The part of decode_kv_read_tokens_total read in trips longer "
            "than ops.paged_attention.READ_CHUNK_TOKENS keys (a read whose "
            "value is wider than that: read_trip_tokens)", ("kind",))
        self._m_kv_unturned = self.registry.counter(
            "decode_kv_unturned_tokens_total",
            "The part of decode_kv_read_tokens_total read by vertices whose "
            "read folds each chunk as gathered, not turned (one K/V head: "
            "ops.paged_attention.paged_gather_rows)", ("kind",))
        # the paged read of each attention vertex, (group, kv_heads, d_v)
        # -> vertices that read so, target and draft
        self._reads = {
            draft: Counter(n._vertex_layer(v).paged_read
                           for v in _transformer.attention_vertices(n))
            for draft, n in ((False, net), (True, draft_net))
            if n is not None}
        token_bytes = float(self.arena.token_nbytes())
        self.registry.gauge(
            "decode_kv_bytes_per_token",
            "Bytes one cached token takes over all the paged pools (K and "
            "V rows of every attention vertex; a latent vertex's one row)"
        ).set_function(lambda: token_bytes)
        if self.state_layers:
            self._m_state_resets = self.registry.counter(
                "decode_state_resets_total",
                "Lanes whose recurrent state a new sequence started from "
                "zero (inside its first prefill program)")
            state_bytes = float(self.arena.state_nbytes())
            self.registry.gauge(
                "decode_state_bytes",
                "Bytes of per-lane recurrent state (convolution tails, SSM "
                "states of the Mamba-2 layers and delta-rule states of the "
                "Kimi delta attention layers) the arena holds"
            ).set_function(lambda: state_bytes)
        if self._counting:
            self._m_moe_routed = self.registry.counter(
                "moe_routed_pairs_total",
                "(token, expert) pairs the expert layers routed, by where "
                "the expert lives: held by this chip, or absent (its part "
                "of the sum is left out)", ("where",))
            self._m_moe_computed = self.registry.counter(
                "moe_computed_pairs_total",
                "Rows the expert layers' grouped product computed, a "
                "tile's padding included")
            self._m_moe_peak = self.registry.counter(
                "moe_expert_load_peak_pairs_total",
                "Pairs of the most loaded held expert, summed over expert "
                "layers and decode steps")
            self._m_moe_steps = self.registry.counter(
                "moe_expert_load_steps_total",
                "(expert layer, step) observations behind "
                "moe_expert_load_peak_pairs_total")
            self._m_moe_touched = self.registry.counter(
                "moe_touched_experts_total",
                "Held experts that got at least one pair, summed over "
                "expert layers and steps: the expert matrices a step "
                "has to read")
            self._m_moe_calls = self.registry.counter(
                "moe_grouped_calls_total",
                "Calls of the expert layers' grouped product: dispatches "
                "x expert layers x steps, counted on the host")
            self._m_moe_grid = self.registry.counter(
                "moe_grid_tiles_total",
                "Tiles of the expert layers' Pallas kernel over its calls, "
                "by part: walked (the gated kernel walks the live tiles "
                "alone, the rows computed over a tile's rows; the grouped "
                "one every tile the arrays are sized for) or skipped (the "
                "rest of that size); counted on the host from the "
                "routing counts a dispatch brings back", ("kernel", "part"))
        if self.eva_layers:
            self._init_eva()
        if self.kda_layers:
            self._init_kda_metrics()
        self._tick_dispatch_wall = 0.0
        self._tick_dispatches = 0
        self._warming = False
        self._precompile: Optional[dict] = None   # warm-up's first pass
        # why the last acquire_lane() refused: "lanes" | "pages" | None
        self.refused_by: Optional[str] = None

    def _build_arenas(self, net, num_pages: int, prefix_cache: bool,
                      kv_dtype) -> None:
        """Allocate what lives on the device beside the weights, and wait
        for it: the arena's pools and per-lane state and, for speculative
        decoding, the draft model's K/V in a pools-only SHADOW arena
        indexed by the same page tables (one admission/eviction decision
        covers both models)."""
        import jax.numpy as jnp

        def arena(net, **kw):
            # the pools' dtype is the dense streaming cache's rule
            # (``_zero_state``), at least f32, so bf16 compute policies
            # keep exact K/V (int8 pools name it as their fp fallback)
            return PagedKVArena(
                self._entries(net), num_pages=num_pages,
                page_size=self.page_size, kv_dtype=kv_dtype,
                dtype=jnp.promote_types(net.policy.compute_dtype,
                                        jnp.float32), **kw)
        self.arena = arena(net, registry=self.registry,
                           prefix_cache=prefix_cache)
        self.draft_arena = (None if self.draft_net is None
                            else arena(self.draft_net, with_allocator=False))
        jax.block_until_ready([
            (a.k_pools, a.v_pools)
            for a in (self.arena, self.draft_arena) if a is not None])

    def _entries(self, net) -> dict:
        """Each stateful vertex of ``net`` (target or draft) in the walker's
        order, with what its arena holds for it (``paged_entry``)."""
        return {name: net._vertex_layer(name).paged_entry(self.lanes)
                for name in _transformer.stateful_vertices(net)}

    # -- construction-time validation ---------------------------------

    def _refuse_unsupported(self, net, *, prefix_cache: bool,
                            draft_net, kv_dtype=None) -> None:
        """What a net with recurrent state, a rotary term or EVA attention
        cannot be served with yet, refused here with the reason."""
        if self.eva_layers and prefix_cache:
            raise ValueError(
                f"prefix_cache=True with EVA vertices "
                f"{self.eva_layers[:2]}...: a prefix hit maps the pages of a "
                "shared prompt prefix, but an EVA lane gives its finished "
                "windows' pages back at each window edge and keeps their "
                "chunks only as summary rows in pages of its own, so a "
                "long prompt's early pages are gone before any other lane "
                "could map them — serve this net with prefix_cache=False")
        if self.eva_layers and draft_net is not None:
            raise ValueError(
                f"draft_net with EVA vertices {self.eva_layers[:2]}...: a "
                "verify chunk writes every draft token's K/V and pools a "
                "chunk its drafts complete into a summary row, and gives "
                "the window's pages back when the drafts cross its edge; a "
                "rejected draft would leave a summary row built from rows "
                "that were never served and a window that cannot come back "
                "(no roll-back of either yet) — serve this net without a "
                "draft_net")
        if self.eva_layers and kv_dtype is not None:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} with EVA vertices: a summary row "
                "lands in a page beside fifteen others written at other "
                "times, and the int8 pools keep one scale a (page, head) "
                "that a later write rescales — serve this net with float "
                "pools")
        if self.state_layers and prefix_cache:
            raise ValueError(
                f"prefix_cache=True with per-lane recurrent state (Mamba-2 "
                f"or Kimi delta attention vertices "
                f"{self.state_layers[:2]}...): a prefix hit maps the K/V "
                "pages of a shared prompt prefix, but a recurrent state "
                "after that prefix is kept nowhere (no snapshot at page "
                "boundaries yet), so the lane would decode from a wrong "
                "state — serve this net with prefix_cache=False")
        if draft_net is not None and (
                self.position_layers
                or _transformer.stateful_vertices(draft_net, "positions")):
            raise ValueError(
                "draft_net with vertices that take absolute positions "
                f"{(self.position_layers or ['the draft net'])[:2]}...: the "
                "speculative programs (draft scan, verify chunk) hand a "
                "layer view-relative positions only, and a rotary term "
                "turned by those is wrong once a window slides or a "
                "prefix is mapped — serve this net without a draft_net")
        if draft_net is not None and (
                self.state_layers
                or _transformer.stateful_vertices(draft_net, "lane_ids")):
            raise ValueError(
                "draft_net with per-lane recurrent state (Mamba-2 or Kimi "
                "delta attention vertices): a rejected draft "
                "token's K/V is simply overwritten, but a recurrent state "
                "that advanced over it cannot be rolled back (no state "
                "roll-back yet) — serve this net without a draft_net")

    @staticmethod
    def _validate_net(net) -> None:
        if not hasattr(net, "topo_order"):
            raise ValueError(
                "paged decode drives a ComputationGraph (transformer_lm)")
        if net.params is None:
            raise ValueError("net is not initialized — call init() first")
        if (len(net.conf.network_inputs) != 1
                or len(net.conf.network_outputs) != 1):
            raise ValueError("paged decode needs exactly one input and "
                             "one output vertex")
        owners = _transformer.stateful_vertices(net)
        if not owners:
            raise ValueError("no causal attention or per-lane state "
                             "vertices — nothing to cache")
        if _transformer.ids_embedding(net) is None:
            raise ValueError(
                "paged decode requires the integer-id input path — build "
                "with transformer_lm(..., input_ids=True)")
        for name in net.topo_order:
            v = net.conf.vertices[name]
            layer = getattr(v, "layer", None)
            if name in owners:
                continue
            if getattr(layer, "paged_inputs", None):    # left out: not causal
                raise ValueError(
                    f"vertex {name!r}: non-causal attention cannot "
                    "decode incrementally")
            if layer is not None and hasattr(layer, "_zero_state"):
                raise ValueError(
                    f"vertex {name!r} ({type(layer).__name__}) carries "
                    "recurrent state — paged decode supports causal "
                    "attention (paged K/V) and the layers that hold a row "
                    "of state a lane (Mamba-2, Kimi delta attention) as "
                    "sequence mixing; an LSTM's carry has no arena")
            if v.init_state(net.policy):
                raise ValueError(
                    f"vertex {name!r} carries persistent state — "
                    "unsupported in paged decode")

    def _check_decode_config(self, net) -> None:
        """The net's own streaming-cache contract must agree with the
        serving window, or served outputs silently diverge from the
        offline oracle: strict layers forbid the sliding window
        outright, and a dense ``max_cache_t`` different from
        ``page_size × pages_per_seq`` means a different attention
        window."""
        for name in _transformer.attention_vertices(net):
            layer = net.conf.vertices[name].layer
            if getattr(layer, "cache_overflow", "evict") == "strict":
                raise ValueError(
                    f"vertex {name!r} sets cache_overflow='strict' — the "
                    "paged serving window slides; serve an evict-mode "
                    "net, or size page_size×pages_per_seq to the full "
                    "context and cap max_new_tokens instead")
            if (layer.max_cache_t is not None
                    and layer.max_cache_t != self.window):
                raise ValueError(
                    f"vertex {name!r} max_cache_t={layer.max_cache_t} != "
                    f"serving window {self.window} (page_size × "
                    "pages_per_seq) — decode through the arena would "
                    "diverge from the net's own streaming semantics")

    # -- lane lifecycle ------------------------------------------------

    def acquire_lane(self, total_tokens: int,
                     prompt=None) -> Optional[int]:
        """Admission: a free lane + a worst-case page reservation
        (``LaneTables.admit``: with the prefix cache and ``prompt`` given,
        the resident full-page prefix is mapped and the feed starts after
        it), or None when either is unavailable (the request stays queued;
        ``refused_by`` then says which: "lanes" or "pages")."""
        self.refused_by = None
        if not self._free_lanes:
            self.refused_by = "lanes"
            return None
        cov = self.lane_tables.admit(self._free_lanes[0], total_tokens,
                                     prompt)
        if cov is None:
            self.refused_by = "pages"
            return None
        lane = self._free_lanes.popleft()
        if self.state_layers:
            # the lane's recurrent state starts from zero inside the
            # sequence's first prefill program (rel_pos 0): no dispatch
            self._m_state_resets.inc()
        if self.arena.prefix_index is not None and prompt is not None:
            covered = self.lane_tables.covered[lane]
            self._m_prefix_hits.inc(
                result="miss" if covered == 0
                else "full" if covered >= len(prompt) else "partial")
            if cov:
                self._m_prefix_pages.inc(cov)
        return lane

    def release_lane(self, lane: int) -> None:
        """Retirement: the lane's pages and unused reservation go back
        (``LaneTables.release``), the lane reusable by the next
        admission."""
        self.lane_tables.release(lane)
        self._free_lanes.append(lane)

    # -- the jitted paged step ----------------------------------------

    def run(self, ids: np.ndarray, write_slots: np.ndarray,
            rel_pos: np.ndarray, tables: np.ndarray, out_rows: np.ndarray,
            lanes: Optional[np.ndarray] = None,
            fed: Optional[np.ndarray] = None) -> np.ndarray:
        """One paged forward over a COMPACT lane selection (``ids
        [B, t_new]``, ``tables [B, P]`` — the scheduler packs only the
        lanes that actually have work, bucketed to a power of two, so a
        single admitting sequence does not pay a full-width prefill):
        scatter the new tokens' K/V, gather, attend, return probs
        ``[B, V]`` on host: lane ``i``'s distribution at position
        ``out_rows[i]`` of its chunk, the one row the scheduler samples
        from (the head is computed at that position only, and a prefill
        chunk of 128 fetches 1/128 of what all its positions would be).
        Pools are donated and replaced, so
        the arena costs one copy of HBM. Jitted once per
        ``(B, t_new, P)`` bucket under a retrace guard — the bucket set
        is fixed (≤ log₂(lanes)+1 sizes × two chunk lengths), so
        steady-state decode never retraces. ``lanes [B]``: the engine
        lane of each row, which a net with per-lane state needs
        (its recurrent state is a row a lane; see
        :meth:`_extra_args`); ``fed [B]``: the positions each lane fed
        (its chunk's length; a padded slot 0), which only an expert
        layer under the prefix cache is told."""
        b, t_new = ids.shape
        name = f"paged_decode[S{b}xT{t_new}xP{self.table_pages}]"
        names = self._extra_paged
        if fed is None:         # no prefix hit among them: what is written
            fed = (write_slots >= 0).sum(axis=1)

        def step(params, k_pools, v_pools, ids, tables, wslots, rel, rows,
                 *extra):
            counts = []
            probs, k_pools, v_pools = _transformer.paged_decode_forward(
                self.net, params, k_pools, v_pools, ids, tables, wslots,
                rel, counts=counts, out_rows=rows, **dict(zip(names, extra)))
            return (probs, *counts, k_pools, v_pools)

        probs, *counts = self._dispatch(
            name, step, self.arena, self.net.params,
            (ids, tables, write_slots, rel_pos,
             np.asarray(out_rows, np.int32),
             *self._extra_args(names, lanes, rel_pos, fed)),
            kind="paged")
        self._note_step("paged", rel_pos, lanes, t_new,
                        (write_slots >= 0).sum(axis=1), 1, counts, b * t_new)
        return probs

    def _extra_args(self, names: tuple, lanes: Optional[np.ndarray],
                    rel: np.ndarray,
                    fed: Optional[np.ndarray] = None) -> tuple:
        """The arrays ``names`` (``_extra`` or ``_extra_paged``) stand
        for, in their order. ``lanes`` None (warm-up) is every slot
        padded: a lane id one past the last lane reads zeros and writes
        nothing, like a sentinel page, and sits at position ``rel``. A
        lane's absolute position is its view-relative one plus what its
        window has evicted."""
        if lanes is None:
            lanes = np.full(len(rel), self.lanes, np.int32)
        lanes = np.asarray(lanes, np.int32)
        by_name = {"lane_ids": lanes, "fed": fed,
                   "positions": self.lane_tables.absolute(lanes, rel)}
        return tuple(np.asarray(by_name[name], np.int32) for name in names)

    def _note_step(self, kind: str, rel: np.ndarray,
                   lanes: Optional[np.ndarray], t_new: int,
                   written: np.ndarray, steps: int, counts: list,
                   tokens: int) -> None:
        """Account one dispatch (``steps`` steps of ``t_new`` positions a
        lane, ``written`` of them real): its reads, its routing (``tokens``
        tokens a step) and its KDA positions and state."""
        if lanes is None:               # every slot padded, as _extra_args
            lanes = np.full(len(rel), self.lanes, np.int32)
        if self.eva_layers:
            self._note_eva(kind, rel, lanes, t_new, written, steps=steps)
        else:
            self._note_kv_read(kind, rel, t_new, steps=steps)
        self._note_routing(counts, steps=steps, tokens=tokens)
        if self.kda_layers:
            self._note_kda(lanes, t_new, written, steps=steps)

    def _note_routing(self, counts: list, steps: int, tokens: int) -> None:
        """Account the expert layers' routing counts that a dispatch of
        ``steps`` decode steps, ``tokens`` tokens a step, brought back
        beside its tokens (``nn.conf.moe.MOE_STATS``), and the tiles of
        the expert kernel's grid they imply."""
        if not counts or self._warming:
            return
        calls = self._counting_layers * steps
        self._m_moe_calls.inc(calls)
        held, absent, computed, peak, observed, touched = (
            int(v) for v in counts[0])
        self._m_moe_routed.inc(held, where="held")
        self._m_moe_routed.inc(absent, where="absent")
        self._m_moe_computed.inc(computed)
        self._m_moe_peak.inc(peak)
        self._m_moe_steps.inc(observed)
        self._m_moe_touched.inc(touched)
        if self._moe_grid is not None:
            kernel, k, e_held, n_published = self._moe_grid
            rows, max_tiles = _moe.tile_grid(tokens * k, e_held, n_published)
            sized = calls * max_tiles
            walked = (computed // rows if kernel == _ffn.GATED_KERNEL_NAME
                      else sized)
            self._m_moe_grid.inc(walked, kernel=kernel, part="walked")
            self._m_moe_grid.inc(sized - walked, kernel=kernel,
                                 part="skipped")

    def _dispatch(self, name: str, step, arena, params, args: tuple, *,
                  kind: str, sync: bool = True) -> list:
        """The ONE copy of the jitted-dispatch protocol every decode
        program goes through: jit ``step`` under the trace-ladder key
        ``name``, call it with ``(params, arena.k_pools, arena.v_pools,
        *args)`` donating the pools, store the returned pools back on
        ``arena``, and account the dispatch. ``step`` must return
        ``(*outputs, k_pools, v_pools)``. A failed dispatch rebuilds
        EVERY arena before re-raising — the pools were donated and may
        already be consumed; the scheduler retires the in-flight batch
        and keeps serving on the fresh pools. ``sync=True`` transfers
        the outputs to host (one host round-trip, counted); ``sync=
        False`` returns them as device arrays (a later sync waits them
        out). The dispatch is timed where its three parts happen:
        enqueue, device wait, fetch (warm-up dispatches are compile
        calls and stay out of every series)."""
        step.__name__ = _module_name(name)
        fn = _xla.keyed_jit(
            self._jit_cache, step, extra=name,
            wrap=lambda f: _xla.retrace_guard(f, name, self.registry),
            donate_argnums=(1, 2))
        if self._precompile is not None:
            return self._record_program(name, fn, arena, params, args, sync)
        hist = None if self._warming else self._m_phase
        wall, nbytes = 0.0, 0
        try:
            with region("engine.enqueue", hist, kind=kind,
                        phase="enqueue") as enqueue:
                *outputs, k_pools, v_pools = fn(
                    params, arena.k_pools, arena.v_pools, *args)
            arena.k_pools = list(k_pools)
            arena.v_pools = list(v_pools)
            wall = enqueue.seconds
            if sync:
                # the sync lives INSIDE the try: on device backends an
                # async kernel failure surfaces here, not at fn() — the
                # rebuild must cover it or the errored pools just stored
                # above would poison every later dispatch (this sync also
                # surfaces failures from earlier sync=False dispatches).
                # np.asarray would have waited for the device anyway:
                # waiting first splits that wait from the copy
                with region("engine.device_wait", hist, kind=kind,
                            phase="device_wait") as wait:
                    jax.block_until_ready(outputs)
                with region("engine.fetch", hist, kind=kind,
                            phase="fetch") as fetch:
                    outputs = [np.asarray(o) for o in outputs]
                wall += wait.seconds + fetch.seconds
                nbytes = sum(o.nbytes for o in outputs)
        except Exception:
            self._reset_all_pools()
            raise
        self._note_dispatch(wall, kind, nbytes, sync=sync)
        return outputs

    def _record_program(self, name: str, fn, arena, params, args: tuple,
                        sync: bool) -> list:
        """Warm-up's first pass (:meth:`warmup`): note the program under
        its ladder key with what it would be called with, dispatch
        nothing, and hand back zeros in the shapes its outputs will
        have, so that the ladder's own code walks on as if it had run."""
        call = (params, arena.k_pools, arena.v_pools, *args)
        self._precompile[name] = (fn, call)
        *outputs, _, _ = fn.__wrapped__.eval_shape(*call)   # the jit itself
        zeros = np.zeros if sync else jax.numpy.zeros
        return [zeros(o.shape, o.dtype) for o in outputs]

    def _reset_all_pools(self) -> None:
        self.arena.reset_pools()
        if self.draft_arena is not None:
            self.draft_arena.reset_pools()
        if self.arena.prefix_index is not None:
            # the cached chains point into pools that just became zeros —
            # serving a hit from them would read garbage
            self.arena.prefix_index.flush()

    def _compile_wall(self) -> float:
        """Total compile wall this engine's registry has seen — deltas
        around a dispatch attribute fresh-trace compiles (a bucket
        ``warmup()`` missed) to the requests that paid for them."""
        h = self.registry.get("xla_compile_seconds")
        return 0.0 if h is None else h.total_sum()

    def _note_dispatch(self, wall: float, kind: str, nbytes: int,
                       sync: bool = True) -> None:
        """Account one dispatch whose phases took ``wall`` seconds in
        all and fetched ``nbytes`` to the host."""
        if self._warming:
            # warmup dispatches are compile calls — folding their
            # multi-second walls into the steady-state tick histogram
            # (or the sync/token ratio) would bury the signal the
            # satellite metric exists to show
            return
        self._tick_dispatch_wall += wall
        self._tick_dispatches += 1
        self._m_dispatches.inc(kind=kind)
        if sync:
            self._m_syncs.inc()
            self._m_tick.observe(wall, component="dispatch")
            self._m_d2h.inc(nbytes, kind=kind)

    def _note_kv_read(self, kind: str, rel: np.ndarray, t_new: int,
                      steps: int = 1, draft: bool = False) -> None:
        """Account how far the paged read of one dispatch went: its
        trip count (the very helper the program's loop bound comes from,
        on the same ``rel``, with each vertex's own trip) for each of the
        block's ``steps``, beside the whole windows the lanes hold; which
        part of it went in trips longer than ``READ_CHUNK_TOKENS``, which
        part was folded as gathered (a vertex with one K/V head), and
        whether the read rounded the chunks it gathered (the read's own
        helper, asked with each vertex's query rows a K/V head; ``draft``:
        the draft net's read). Where vertices take different trips the counts
        are their mean: the read of one vertex."""
        if self._warming:
            return
        ps, pps = self.page_size, self.pages_per_seq
        reads = self._reads[draft]
        visited = long_trip = unturned = 0
        for (g, h, d_v), count in reads.items():
            rows = len(rel) * h * t_new * g
            chunk = ps * _paged.read_chunk_pages(ps, pps, d_v, rows)
            seen = count * sum(
                min(self.window, chunk * int(_paged.read_trip_count(
                    rel + i, t_new, ps, pps, np, d_v, rows)))
                for i in range(steps))
            visited += seen
            long_trip += seen if chunk > _paged.READ_CHUNK_TOKENS else 0
            unturned += seen if h == 1 else 0
        vertices = max(1, sum(reads.values()))  # per-lane state only: 0
        visited, long_trip, unturned = (
            visited / vertices, long_trip / vertices, unturned / vertices)
        self._m_kv_read.inc(len(rel) * visited, kind=kind)
        self._m_kv_long_trip.inc(len(rel) * long_trip, kind=kind)
        self._m_kv_unturned.inc(len(rel) * unturned, kind=kind)
        self._m_kv_window.inc(len(rel) * self.window * steps, kind=kind)
        net, arena = ((self.draft_net, self.draft_arena) if draft
                      else (self.net, self.arena))
        pool_dtype = None if arena.kv_dtype == "int8" else arena.dtype
        if all(_paged.read_rounds_chunk(net.policy.compute_dtype, pool_dtype,
                                        t_new * g) for g, _, _ in reads):
            self._m_kv_rounded.inc(len(rel) * visited, kind=kind)

    def _init_eva(self) -> None:
        """What the EVA vertices' reads and pages are counted in: on the
        host, from what the engine dispatches, as
        ``decode_kv_read_tokens_total`` is (:meth:`_note_eva`)."""
        reg, self._m_eva = self.registry, {}
        self._m_eva["rows"] = reg.counter(
            "eva_read_rows_total",
            "K/V rows the EVA reads of the dispatches visited, summed over "
            "the EVA vertices, by part: the window's raw rows and the "
            "summary rows (live lanes x trips x chunk rows, for every step "
            "of a block; ops.paged_attention.eva_read_trips)", ("part",))
        self._m_eva["bytes"] = reg.counter(
            "eva_read_bytes_total",
            "Bytes of K and V rows the same reads gathered")
        self._m_eva["written"] = reg.counter(
            "eva_summary_rows_written_total",
            "Summary rows written: chunks the dispatches completed, summed "
            "over the EVA vertices")
        self._m_eva["attended"] = reg.counter(
            "eva_attended_rows_total",
            "Rows one EVA vertex's softmax takes, summed over the written "
            "positions: the own window's rows up to the position and the "
            "summary rows of every chunk before that window")
        self._m_eva["held"] = reg.counter(
            "eva_held_rows_total",
            "Rows of the pages the dispatched EVA lanes held (window and "
            "summary pages x page size), summed over dispatches")
        self._m_eva["context"] = reg.counter(
            "eva_context_tokens_total",
            "Positions the same lanes had fed after the dispatch, summed "
            "over dispatches")

    def _note_eva(self, kind: str, rel: np.ndarray,
                  lanes: Optional[np.ndarray], t_new: int,
                  written: np.ndarray, steps: int = 1) -> None:
        """Account an EVA dispatch: the rows its reads visited by part
        (the bounds the program's loops take, from the same helper and the
        same arrays, for each of a block's ``steps``), the summary rows its
        lanes' ``written`` positions completed, the rows their softmaxes
        took, and the pages they hold against the positions fed."""
        if self._warming:
            return
        live = np.asarray(lanes) < self.lanes
        t = self.lane_tables
        rel = np.asarray(rel, np.int64)
        pos, n = t.absolute(lanes, rel), np.asarray(written, np.int64)
        ps, chunk, window = self.page_size, t.eva_chunk, t.eva_window
        layers, lanes_live = len(self.eva_layers), int(live.sum())
        window_rows = summary_rows = 0
        for (_, h, d_v), count in self._reads[False].items():
            rows = len(rel) * h * t_new
            rows_w = ps * _paged.read_chunk_pages(ps, t.win_pages, d_v, rows)
            rows_s = ps * _paged.read_chunk_pages(ps, t.sum_pages, d_v, rows)
            for i in range(steps):
                first, end, trips = _paged.eva_read_trips(
                    rel + i, pos + i, t_new, window, chunk, ps,
                    t.win_pages, t.sum_pages, np, d_v, rows)
                window_rows += (count * lanes_live
                                * max(0, int(end - first)) * rows_w)
                summary_rows += count * lanes_live * int(trips) * rows_s
        self._m_eva["rows"].inc(window_rows, part="window")
        self._m_eva["rows"].inc(summary_rows, part="summary")
        row_bytes = 2 * int(self.arena.k_pools[0].shape[-1]) \
            * self.arena.k_pools[0].dtype.itemsize
        self._m_eva["bytes"].inc((window_rows + summary_rows) * row_bytes)
        pos, n, idx = pos[live], n[live], np.asarray(lanes)[live]
        self._m_eva["written"].inc(
            int(((pos + n) // chunk - pos // chunk).sum()) * layers)
        fed = np.concatenate([np.arange(p, p + m) for p, m in zip(pos, n)]
                             + [np.zeros(0, np.int64)])
        start = fed // window * window
        self._m_eva["attended"].inc(int((fed - start + 1 + start // chunk)
                                     .sum()))
        self._m_eva["held"].inc(ps * sum(t.held_pages(i) for i in idx))
        self._m_eva["context"].inc(int((pos + n).sum()))

    def _init_kda_metrics(self) -> None:
        """What the Kimi delta attention vertices compute and carry: on
        the host, from what the engine dispatches (:meth:`_note_kda`)."""
        self._m_kda_positions = self.registry.counter(
            "kda_positions_total",
            "Positions the Kimi delta attention vertices computed, summed "
            "over them, by form (chunked: a dispatch of more than one new "
            "token a lane, in chunks; step: the recurrence step of a "
            "one-token dispatch or of a fused block) and kind (valid: a "
            "position a real lane wrote; pad: a bucket's empty slot, a "
            "chunk's tail beyond a lane's last valid token, a finished "
            "lane's step in a block)", ("form", "kind"))
        self._m_kda_state = self.registry.counter(
            "kda_state_bytes_total",
            "Bytes of per-lane state (delta-rule state and convolution "
            "tail) the Kimi delta attention vertices read and wrote: twice "
            "a lane's state for every real lane of a dispatch, every step "
            "and every such vertex")
        # a lane's state over all the KDA vertices, float32
        self._kda_lane_bytes = 4 * sum(
            int(np.prod(shape))
            for name in self.kda_layers
            for shape in self.net._vertex_layer(name).state_shapes(1))

    def _note_kda(self, lanes: Optional[np.ndarray], t_new: int,
                  written: np.ndarray, steps: int = 1) -> None:
        """Account a dispatch of ``steps`` steps of ``t_new`` positions
        over a bucket of ``len(written)`` slots for the KDA vertices:
        the positions computed (every slot, every step), of which
        ``written`` a lane were a real lane's, and the state the real
        lanes read and wrote at every step."""
        if self._warming:
            return
        layers = len(self.kda_layers)
        form = "step" if t_new == 1 else "chunked"
        valid = int(np.sum(written))
        self._m_kda_positions.inc(layers * valid, form=form, kind="valid")
        self._m_kda_positions.inc(
            layers * (len(written) * t_new * steps - valid), form=form,
            kind="pad")
        real = int(np.sum(np.asarray(lanes) < self.lanes))
        self._m_kda_state.inc(2 * real * steps * self._kda_lane_bytes)

    # -- fused multi-token block --------------------------------------

    def run_fused(self, last: np.ndarray, tables: np.ndarray,
                  rel: np.ndarray, active: np.ndarray, budget: np.ndarray,
                  eos: np.ndarray, temps: np.ndarray, top_k: np.ndarray,
                  top_p: np.ndarray, uniforms: np.ndarray,
                  lanes: Optional[np.ndarray] = None
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One fused block: ``uniforms.shape[1]`` decode steps in ONE
        dispatch through ``models.transformer.fused_decode_loop`` —
        on-device sampling and EOS/budget self-retire included. One
        host sync per block (the satellite ``decode_host_syncs_total``
        measures). Returns host ``(tokens [B, N], valid [B, N],
        n_emitted [B])``."""
        b, n = uniforms.shape
        name = f"fused_decode[S{b}xN{n}xP{self.table_pages}]"

        names = self._extra

        def step(params, k_pools, v_pools, last, tables, rel, active,
                 budget, eos, temps, tk, tp, u, *extra):
            return _transformer.fused_decode_loop(
                self.net, params, k_pools, v_pools, last, tables, rel,
                active, budget, eos, temps, tk, tp, u,
                **dict(zip(names, extra)))

        toks, valid, n_emitted, _done, *counts = self._dispatch(
            name, step, self.arena, self.net.params,
            (last, tables, rel, active, budget, eos, temps, top_k, top_p,
             uniforms, *self._extra_args(names, lanes, rel)), kind="fused")
        # the block's loop ends with its last live lane
        self._note_step("fused", rel, lanes, 1, n_emitted,
                        int(n_emitted.max()), counts, b)
        return toks, valid, n_emitted

    # -- speculative draft / verify -----------------------------------

    def run_draft_prefill(self, ids: np.ndarray, write_slots: np.ndarray,
                          rel_pos: np.ndarray, tables: np.ndarray,
                          out_rows: np.ndarray) -> None:
        """Shadow prefill: the draft model processes the SAME prompt
        chunk into its own pools (same tables, same slots), so its first
        drafting block sees the full context. Output discarded — no host
        sync; an async failure surfaces at the block's verify sync.
        ``out_rows`` as in :meth:`run`: the program builds no
        ``[B, t, V]`` for an output nobody reads."""
        b, t = ids.shape
        name = f"draft_prefill[S{b}xT{t}xP{self.table_pages}]"

        def step(params, k_pools, v_pools, ids, tables, wslots, rel, rows):
            return _transformer.paged_decode_forward(
                self.draft_net, params, k_pools, v_pools, ids, tables,
                wslots, rel, out_rows=rows)

        self._dispatch(name, step, self.draft_arena,
                       self.draft_net.params,
                       (ids, tables, write_slots, rel_pos,
                        np.asarray(out_rows, np.int32)),
                       kind="draft_prefill", sync=False)
        self._note_kv_read("draft_prefill", rel_pos, t, draft=True)

    def run_draft(self, last: np.ndarray, tables: np.ndarray,
                  rel: np.ndarray, active: np.ndarray,
                  write_budget: np.ndarray, temps: np.ndarray,
                  top_k: np.ndarray, top_p: np.ndarray,
                  uniforms: np.ndarray):
        """Draft half of a speculative block: K+1 fused steps of the
        draft net (``uniforms [B, K+1]``). Returns DEVICE arrays
        ``(draft_tokens [B, K], draft_dists [B, K, V])`` — they feed
        straight into :meth:`run_verify` with no host sync between."""
        b, k1 = uniforms.shape
        name = f"spec_draft[S{b}xK{k1 - 1}xP{self.table_pages}]"

        def step(params, k_pools, v_pools, last, tables, rel, active,
                 wbudget, temps, tk, tp, u):
            return _transformer.draft_decode_loop(
                self.draft_net, params, k_pools, v_pools, last, tables,
                rel, active, wbudget, temps, tk, tp, u)

        d_toks, d_dists = self._dispatch(
            name, step, self.draft_arena, self.draft_net.params,
            (last, tables, rel, active, write_budget, temps, top_k,
             top_p, uniforms), kind="draft", sync=False)
        self._note_kv_read("draft", rel, 1, steps=k1, draft=True)
        return d_toks, d_dists

    def run_verify(self, last: np.ndarray, tables: np.ndarray,
                   rel: np.ndarray, active: np.ndarray,
                   write_budget: np.ndarray, d_toks, d_dists,
                   temps: np.ndarray, top_k: np.ndarray,
                   top_p: np.ndarray, u_accept: np.ndarray,
                   u_fix: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Verify half: one batched K+1 target chunk + on-device
        accept/reject/bonus (``models.transformer.spec_verify``). The
        block's ONE host sync happens here (it also waits out the draft
        dispatch). Returns host ``(emitted [B, K+1], valid [B, K+1],
        accepts [B])``."""
        b, k = u_accept.shape
        name = f"spec_verify[S{b}xK{k}xP{self.table_pages}]"

        def step(params, k_pools, v_pools, last, tables, rel, active,
                 wbudget, d_toks, d_dists, temps, tk, tp, ua, uf):
            return _transformer.spec_verify(
                self.net, params, k_pools, v_pools, last, tables, rel,
                active, wbudget, d_toks, d_dists, temps, tk, tp, ua, uf)

        emitted, valid, accepts = self._dispatch(
            name, step, self.arena, self.net.params,
            (last, tables, rel, active, write_budget, d_toks, d_dists,
             temps, top_k, top_p, u_accept, u_fix), kind="verify")
        self._note_kv_read("verify", rel, k + 1)
        return emitted, valid, accepts

    def warmup(self) -> None:
        """Compile the entire fixed trace set — every power-of-two lane
        bucket × the chunk/block shapes the configured mode actually
        dispatches (prefill chunk always; the t=1 ticked step OR the
        fused block OR the draft-prefill/draft/verify triple) — up
        front, so serving cold-start pays compilation here instead of on
        the first live requests. Warmup dispatches carry all-sentinel
        tables and dropped write slots, so they cannot perturb the
        arena.

        The ladder is walked twice. The first pass dispatches nothing: it
        notes each program with its arguments (:meth:`_record_program`),
        and the programs are then lowered and compiled CONCURRENTLY, one
        a thread (XLA compiles outside the interpreter's lock; a fused
        block's compile is 25 s, most of it the sampler's sort over the
        vocabulary, and a ladder of twelve took 200 s one after another).
        The second pass is the ladder as it always ran: each program's
        first call finds its executable compiled (an ahead-of-time compile
        and the call share JAX's compilation cache) and runs once.

        Timed as the ``warmup`` phase of ``startup_phase_seconds`` and its
        three parts, ``warmup.plan``, ``warmup.compile`` (the pool's wall)
        and ``warmup.run``; each task of the pool is the program's sample
        in ``xla_compile_seconds{fn=<ladder key>}``
        (``retrace_guard``'s ``precompile``)."""
        from concurrent.futures import ThreadPoolExecutor

        def phase(name):
            return _xla.startup_region(name, self.registry)

        self._warming = True
        try:
            with phase("startup.warmup"):
                self._precompile = {}
                try:
                    with phase("warmup.plan"):
                        self._warmup_ladder()
                finally:
                    programs, self._precompile = self._precompile, None
                with phase("warmup.compile") as compiling, \
                        ThreadPoolExecutor(min(len(programs), 12)) as pool:
                    list(pool.map(
                        lambda p: p[0].precompile(p[1], compiling.span),
                        programs.values()))
                with phase("warmup.run"):
                    self._warmup_ladder()
        finally:
            self._warming = False

    def _warmup_ladder(self) -> None:
        b = 1
        while True:
            c = self.prefill_chunk
            sentinel_tables = np.full((b, self.table_pages),
                                      self.arena.sentinel, np.int32)
            inactive = np.zeros(b, bool)
            zeros_f = np.zeros(b, np.float32)
            zeros_i = np.zeros(b, np.int32)

            def feed(t, run=self.run):      # a chunk of t, every slot padded
                run(np.zeros((b, t), np.int32), np.full((b, t), -1, np.int32),
                    zeros_i, sentinel_tables, zeros_i)
            feed(c)
            if self.arena.prefix_index is not None and c > 1:
                # prefix-cache hit ticks re-feed at t=1 (the scheduler
                # collapses an all-≤1-token prefill tick to the decode
                # shape) — compile it in every mode or the first hit
                # pays a mid-serve trace
                feed(1)
                if self.draft_net is not None:
                    feed(1, self.run_draft_prefill)
            if self.draft_net is not None:
                feed(c, self.run_draft_prefill)
                d_toks, d_dists = self.run_draft(
                    zeros_i, sentinel_tables, zeros_i, inactive, zeros_i,
                    zeros_f, zeros_i, np.ones(b, np.float32),
                    np.zeros((b, self.draft_k + 1), np.float32))
                self.run_verify(
                    zeros_i, sentinel_tables, zeros_i, inactive, zeros_i,
                    d_toks, d_dists, zeros_f, zeros_i,
                    np.ones(b, np.float32),
                    np.zeros((b, self.draft_k), np.float32),
                    np.zeros((b, self.draft_k + 1), np.float32))
            elif self.block_len > 1:
                self.run_fused(
                    zeros_i, sentinel_tables, zeros_i, inactive, zeros_i,
                    np.full(b, -1, np.int32), zeros_f, zeros_i,
                    np.ones(b, np.float32),
                    np.zeros((b, self.block_len), np.float32))
            else:
                feed(1)
            if b >= self.lanes:
                break
            b <<= 1           # same ladder _compact produces

    # -- model swap (fenced by the scheduler) -------------------------

    def swap_net(self, net) -> None:
        """Replace the served model at a step boundary. The topology must
        match (same vertices, same param shapes) — paged state is laid
        out per attention vertex; a different graph would silently
        mis-read it. Clears the trace cache (the old traces closed over
        the old net object)."""
        self._validate_net(net)
        self._check_decode_config(net)
        if list(net.topo_order) != list(self.net.topo_order):
            raise ValueError("model swap with a different graph topology")
        import jax
        old_shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape),
                                            self.net.params)
        new_shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape),
                                            net.params)
        if old_shapes != new_shapes:
            raise ValueError("model swap with different parameter shapes")
        self.net = net
        self._jit_cache.clear()
        if self.arena.prefix_index is not None:
            # cached K/V was computed by the OLD params — a post-swap
            # prefix hit would silently decode against the wrong model
            self.arena.prefix_index.flush()
        # recompile the trace ladder NOW, while the caller holds the
        # fence — otherwise the first post-swap requests pay per-bucket
        # compilation inside the decode loop with their deadlines burning
        self.warmup()

    def lanes_free(self) -> int:
        return len(self._free_lanes)


class DecodeScheduler:
    """The continuous-batching loop (see module docstring).

    Every tick: retire expired/finished sequences → admit from the
    bounded queue against lanes + page reservations → ONE batched prefill
    chunk for admitting sequences → ONE decode step for every decoding
    sequence. ``step_once()`` is public so deterministic tests drive the
    whole machine on a :class:`ManualClock` with no threads.
    """

    def __init__(self, engine: PagedDecodeEngine, *, max_queue: int = 64,
                 default_max_new_tokens: int = 32,
                 request_timeout_s: float = 30.0,
                 clock: Clock = SYSTEM_CLOCK,
                 registry: Optional[_metrics.MetricsRegistry] = None,
                 tracer=None, start_thread: bool = True):
        self.engine = engine
        self.max_queue = int(max_queue)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.request_timeout_s = float(request_timeout_s)
        self.clock = clock
        self.tracer = tracer
        self.registry = registry if registry is not None else engine.registry
        self._init_metrics()
        self._queue: deque = deque()
        self._cond = threading.Condition()
        self._active: Dict[int, _Sequence] = {}
        # held across one full tick: the step boundary every outside
        # mutation (drain bookkeeping, model swap) must fence on
        self._dispatch_lock = threading.RLock()
        self._draining = False
        self._stopped = False
        self._thread: Optional[threading.Thread] = None
        if start_thread:
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def _init_metrics(self) -> None:
        reg = self.registry
        # same family the wave path sheds into — one pane of glass
        self._m_shed = reg.counter(
            "serving_shed_total",
            "Predict requests shed with 503 before reaching the model",
            ("reason",))
        self._m_admitted = reg.counter(
            "decode_admitted_total",
            "Generative sequences admitted into the decode batch")
        self._m_retired = reg.counter(
            "decode_retired_total",
            "Generative sequences retired, by reason", ("reason",))
        self._m_steps = reg.counter(
            "decode_steps_total", "Batched decode steps dispatched")
        self._m_tokens = reg.counter(
            "decode_tokens_total",
            "Tokens pushed through the paged decode path", ("phase",))
        self._m_occupancy = reg.histogram(
            "decode_batch_occupancy",
            "Sequences active in each batched decode step",
            buckets=[float(1 << i) for i in range(11)])
        self._m_ttft = reg.histogram(
            "decode_ttft_seconds",
            "Submit → first generated token (queue + prefill)")
        self._m_tpot = reg.histogram(
            "decode_time_per_output_token_seconds",
            "Steady-state seconds per output token, per finished sequence",
            buckets=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0])
        self._m_ttft_part = reg.histogram(
            "decode_ttft_component_seconds",
            "The four parts of each request's TTFT (their sums add up to "
            "decode_ttft_seconds): queue_wait, prefill, compile, "
            "dispatch; blocked_by says what the last refused admission "
            "pass lacked while the request queued (queue_wait only)",
            ("component", "blocked_by"))
        self._m_gap = reg.histogram(
            "decode_delivery_gap_seconds",
            "Time between consecutive deliveries of tokens to one "
            "request (a fused block hands block_len tokens at once, "
            "which the per-token mean averages away)",
            buckets=[0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0])
        self._m_wait = reg.histogram(
            "decode_sched_wait_seconds",
            "Scheduler loop asleep: idle (no queue, nothing active) or "
            "blocked (queued work, nothing admissible, nothing running)",
            ("why",),
            buckets=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1])
        self._m_draft = reg.counter(
            "decode_draft_tokens_total",
            "Speculative draft tokens, by verify outcome", ("result",))
        # goodput, not just throughput: tokens that were SERVED split by
        # whether their request met its SLO deadline — a saturated
        # scheduler can post high decode_tokens_total while every
        # request deadline-expires half-answered
        self._m_goodput = reg.counter(
            "decode_goodput_tokens_total",
            "Generated tokens by SLO outcome of their request: met "
            "(finished by eos/max_tokens within its deadline) vs missed "
            "(deadline/error/shutdown)", ("slo",))
        # weakly bound, like the arena gauges: a retired scheduler (and
        # through it the engine, params, and pools) must stay
        # collectable even on a shared registry — a dead ref raises,
        # dropping the series at exposition
        ref = weakref.ref(self)

        def _sample(get):
            def fn():
                sched = ref()
                if sched is None:
                    raise LookupError("scheduler retired")
                return float(get(sched))
            return fn

        reg.gauge(
            "decode_active_sequences",
            "Generative sequences currently holding a decode lane"
        ).set_function(_sample(lambda s: len(s._active)))
        reg.gauge(
            "decode_queue_depth",
            "Generative requests accepted but not yet admitted"
        ).set_function(_sample(lambda s: len(s._queue)))

    # -- intake --------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens: Optional[int] = None, *,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               timeout_s: Optional[float] = None,
               seed: Optional[int] = None, top_k: int = 0,
               top_p: float = 1.0, trace_ctx=None) -> DecodeRequest:
        """Accept one generative request into the bounded queue. Raises
        :class:`SchedulerDraining` / :class:`SchedulerSaturated` (the
        shed paths — recorded by reason) instead of queueing unbounded
        latency. ``top_k``/``top_p`` filter temperature sampling (the
        one semantics shared by the host sampler and the fused device
        loop — see ``ops/sampling.py``); ignored when greedy.

        With a tracer attached, every request gets a root span
        (``decode.request``) with child spans for queue wait, each
        prefill chunk, and each decode/spec block dispatch — the
        per-request timeline ``/debug/timeline`` and
        ``util.timeline.request_timelines`` render; their durations are
        the numbers the histograms observe. ``trace_ctx`` (a
        traceparent string or extracted SpanContext, e.g. from an HTTP
        header) parents the root span on the caller's trace."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if self.engine.vocab and (prompt.min() < 0
                                  or prompt.max() >= self.engine.vocab):
            raise ValueError(
                f"prompt ids outside [0, {self.engine.vocab})")
        n_new = int(max_new_tokens if max_new_tokens is not None
                    else self.default_max_new_tokens)
        if n_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        eng = self.engine
        if (prompt.size + n_new > eng.window
                and (eng.state_layers or eng.position_layers)):
            why = ("the attention layers' pages slide while the recurrent "
                   "layers' state has no window, so the two would see "
                   "different histories" if eng.state_layers else
                   "the pages slide and the first tokens leave the "
                   "attention, but a rotary model is trained, and its "
                   "reference computed, with every earlier position in "
                   "view, so what it would generate past the window is no "
                   "longer the model's")
            raise ValueError(
                f"request of {prompt.size} + {n_new} tokens exceeds the "
                f"window of {eng.window}: past it {why} — size page_size x "
                "pages_per_seq to the longest request")
        if int(top_k) < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if not (0.0 < float(top_p) <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # top_k >= vocab filters nothing — normalize to 0 so the value
        # stays int32-safe in the device block arrays (an unbounded
        # client value would OverflowError inside the tick and
        # error-retire every in-flight sequence)
        top_k = int(top_k)
        if self.engine.vocab and top_k >= self.engine.vocab:
            top_k = 0
        rng = (np.random.default_rng(seed) if temperature > 0 else None)
        req = DecodeRequest(
            prompt, n_new, temperature, eos_id,
            Deadline(timeout_s if timeout_s is not None
                     else self.request_timeout_s, self.clock),
            rng, self.clock.monotonic(), top_k=int(top_k),
            top_p=float(top_p))
        if self.tracer is not None:
            if isinstance(trace_ctx, str):
                trace_ctx = _tracing.extract(trace_ctx)
            req.span = self.tracer.start(
                "decode.request", parent=trace_ctx,
                attributes={"prompt_len": int(prompt.size),
                            "max_new_tokens": n_new})
        try:
            with self._cond:
                # flags checked under the lock: a submit racing stop()
                # must either land before the shutdown flush or be
                # refused — never strand a request in a queue nothing
                # will ever drain
                if self._draining or self._stopped:
                    self._m_shed.inc(reason="draining")
                    _flight.record("decode_shed", reason="draining")
                    raise SchedulerDraining("decode scheduler is draining")
                if len(self._queue) >= self.max_queue:
                    self._m_shed.inc(reason="decode_queue_full")
                    _flight.record("decode_shed",
                                   reason="decode_queue_full",
                                   queue_depth=len(self._queue))
                    raise SchedulerSaturated(
                        "decode queue full", retry_after=1.0)
                self._queue.append(req)
                self._cond.notify_all()
        except Exception:
            self._end_request_spans(req, "shed")
            raise
        return req

    def _end_request_spans(self, req: DecodeRequest,
                           status: Optional[str] = None) -> None:
        if req.span is None:
            return
        if req.t_admit is None:       # died in the queue (or at its door)
            self._record_queue_span(req, self.clock.monotonic(), status)
        req.span.end(status)

    def _record_queue_span(self, req: DecodeRequest, until: float,
                           status: Optional[str] = None, **attrs) -> None:
        """The request's ``queue`` span: submit → ``until`` on the
        scheduler's clock, the number ``queue_wait`` reports."""
        self.tracer.record(
            "queue", until - req.t_submit, parent=req.span, status=status,
            attributes={"blocked_by": req.blocked_by, **attrs})

    # -- the continuous-batching tick ---------------------------------

    def step_once(self) -> bool:
        """One scheduler tick: retire → admit → prefill chunk → decode
        step. Returns whether anything progressed. Dispatch errors retire
        every in-flight sequence with ``finish_reason="error"`` and leave
        the scheduler serving (the arena's masks make recycled pages
        safe for the next admissions)."""
        with self._dispatch_lock:
            eng = self.engine
            eng._tick_dispatch_wall = 0.0
            eng._tick_dispatches = 0
            with region("sched.tick") as tick:
                with region("sched.retire_expired"):
                    progressed = self._retire_expired()
                with region("sched.admit"):
                    progressed = self._admit() or progressed
                try:
                    progressed = self._prefill_tick() or progressed
                    progressed = self._decode_tick() or progressed
                except Exception as e:  # noqa: BLE001 — keep serving
                    _flight.record("decode_error",
                                   error=f"{type(e).__name__}: {e}",
                                   in_flight=len(self._active))
                    for seq in list(self._active.values()):
                        seq.req.error = f"{type(e).__name__}: {e}"
                        self._retire(seq, "error")
                    progressed = True
            # the measured split behind the fused-block design: dispatch
            # wall (device compute + sync, observed per dispatch by the
            # engine) vs everything else this tick did on the host —
            # only ticks that dispatched count, so idle polling doesn't
            # flood the bookkeeping series
            if eng._tick_dispatches:
                eng._m_tick.observe(
                    max(0.0, tick.seconds - eng._tick_dispatch_wall),
                    component="bookkeeping")
            return progressed

    def _retire_expired(self) -> bool:
        any_ = False
        for seq in list(self._active.values()):
            if seq.req.deadline.expired:
                self._retire(seq, "deadline")
                any_ = True
        with self._cond:
            queued = list(self._queue)
        for req in queued:
            if req.deadline.expired:
                with self._cond:
                    try:
                        self._queue.remove(req)
                    except ValueError:
                        continue
                self._finish(req, "deadline")
                self._m_retired.inc(reason="deadline")
                any_ = True
        return any_

    def _admit(self) -> bool:
        admitted = False
        while True:
            with self._cond:
                if not self._queue:
                    break
                req = self._queue[0]
            lane = self.engine.acquire_lane(
                len(req.prompt) + req.max_new_tokens, prompt=req.prompt)
            if lane is None:          # no lane / page pressure: stay queued
                # the head holds up everything behind it: the whole queue
                # waited out this pass for the same want
                with self._cond:
                    for waiting in self._queue:
                        waiting.blocked_by = self.engine.refused_by
                break
            with self._cond:
                self._queue.popleft()
            req.t_admit = self.clock.monotonic()
            if req.span is not None:
                self._record_queue_span(req, req.t_admit, lane=lane)
            seq = _Sequence(req, lane)
            # prefix-cache hit: the engine parked the feed cursor past
            # the covered tokens (a full cover re-feeds the last prompt
            # token with its write dropped)
            seq.cursor = int(self.engine.lane_tables.pos[lane])
            seq.covered = int(self.engine.lane_tables.covered[lane])
            req.prefix_covered_tokens = min(seq.covered, len(req.prompt))
            self._active[lane] = seq
            self._m_admitted.inc()
            admitted = True
        return admitted

    def _compact(self, seqs: List[_Sequence], t_new: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                            np.ndarray, np.ndarray]:
        """Pack the lanes that actually have work into a power-of-two
        batch bucket: a lone admission prefills at [1, C] cost, not a
        full-width padded dispatch, and the tail of a draining batch
        decodes at [1..] cost — while the bucket SET stays fixed, so the
        retrace pin holds."""
        eng = self.engine
        b = 1
        while b < len(seqs):
            b <<= 1
        ids = np.zeros((b, t_new), np.int32)
        wslots = np.full((b, t_new), -1, np.int32)
        rel = np.zeros(b, np.int32)
        tables = np.full((b, eng.table_pages), eng.arena.sentinel,
                         np.int32)
        lanes = np.full(b, eng.lanes, np.int32)     # padded slot: no lane
        for i, seq in enumerate(seqs):
            tables[i] = eng.lane_tables.tables[seq.lane]
            lanes[i] = seq.lane
        return ids, wslots, rel, tables, lanes

    def _prefill_tick(self) -> bool:
        seqs = [s for s in self._active.values() if s.state == _PREFILL]
        if not seqs:
            return False
        with region("sched.prefill"):
            self._prefill_chunk(seqs)
        return True

    def _prefill_chunk(self, seqs: List[_Sequence]) -> None:
        eng = self.engine
        c = eng.prefill_chunk
        chunk_len: List[int] = []
        for seq in seqs:
            n = min(c, len(seq.req.prompt) - seq.cursor)
            eng.lane_tables.ensure(seq.lane, n)
            chunk_len.append(n)
        # prefix-cache fast path: when every admitting lane has at most
        # one token left to feed (the full-hit re-feed), dispatch at the
        # t=1 decode shape instead of the padded prefill chunk — hit
        # TTFT collapses to one decode-step cost (warmup compiles [b,1]
        # in every mode when the cache is on, so the retrace pin holds)
        t_feed = (1 if (eng.arena.prefix_index is not None
                        and max(chunk_len) <= 1) else c)
        ids, wslots, rel, tables, lanes = self._compact(seqs, t_feed)
        rows = np.zeros(len(rel), np.int32)
        for i, seq in enumerate(seqs):
            n = chunk_len[i]
            r = eng.lane_tables.rel_pos(seq.lane)
            ids[i, :n] = seq.req.prompt[seq.cursor:seq.cursor + n]
            slots = r + np.arange(n)
            if seq.covered > seq.cursor:
                # covered positions are cache-resident: re-fed tokens
                # there attend (their K/V is in the gathered view) but
                # must NOT write — a write would touch a shared page
                slots[:seq.covered - seq.cursor] = -1
            wslots[i, :n] = slots
            rel[i] = r
            # the one position whose distribution may be sampled from:
            # the chunk's last prompt token (a padded slot keeps row 0)
            rows[i] = n - 1
        _faults.check("serving.decode_step",
                      {"phase": "prefill", "lanes": len(seqs)})
        w0, c0 = eng._tick_dispatch_wall, eng._compile_wall()
        fed = np.zeros(len(rel), np.int32)
        fed[:len(seqs)] = chunk_len
        probs = eng.run(ids, wslots, rel, tables, rows, lanes, fed)  # [B, V]
        if eng.draft_net is not None:
            # shadow prefill: the draft cache must hold the same prompt
            # context before its first drafting block (same ids, same
            # slots, its own pools)
            eng.run_draft_prefill(ids, wslots, rel, tables, rows)
        # TTFT attribution: this chunk's dispatch wall (compile split
        # out) is charged to every sequence it prefilled
        d_wall = eng._tick_dispatch_wall - w0
        d_compile = min(eng._compile_wall() - c0, d_wall)
        for i, seq in enumerate(seqs):
            seq.prefill_s += d_wall - d_compile
            seq.compile_s += d_compile
            if self.tracer is not None and seq.req.span is not None:
                self.tracer.record(
                    "prefill_chunk", d_wall, parent=seq.req.span,
                    attributes={"lane": seq.lane, "bucket": ids.shape[0],
                                "tokens": int(chunk_len[i]),
                                "compile_s": round(d_compile, 6),
                                "state_layers": len(eng.state_layers)})
        self._m_tokens.inc(sum(chunk_len), phase="prefill")
        for i, seq in enumerate(seqs):
            n = chunk_len[i]
            eng.lane_tables.advance(seq.lane, n)
            seq.cursor += n
            if seq.cursor == len(seq.req.prompt):
                # publish the prompt's full-page prefix to the cache
                # BEFORE emitting (emit may retire the lane and release
                # its pages); a hit re-registers only as an LRU touch
                eng.lane_tables.register_prefix(seq.lane, seq.req.prompt)
                # the last prompt position's distribution yields the
                # FIRST generated token (TTFT lands here)
                self._emit_token(seq, probs[i])
                if seq.lane in self._active:
                    seq.state = _DECODE

    def _decode_tick(self) -> bool:
        seqs = [s for s in self._active.values() if s.state == _DECODE]
        if not seqs:
            return False
        eng = self.engine
        with region("sched.decode"):
            if eng.draft_net is not None:
                self._spec_block_tick(seqs)
            elif eng.block_len > 1:
                self._fused_block_tick(seqs)
            else:
                self._ticked_step(seqs)
        return True

    def _ticked_step(self, seqs: List[_Sequence]) -> None:
        eng = self.engine
        for seq in seqs:
            eng.lane_tables.ensure(seq.lane, 1)
        ids, wslots, rel, tables, lanes = self._compact(seqs, 1)
        for i, seq in enumerate(seqs):
            r = eng.lane_tables.rel_pos(seq.lane)
            ids[i, 0] = seq.last_token
            wslots[i, 0] = r
            rel[i] = r
        _faults.check("serving.decode_step",
                      {"phase": "decode", "lanes": len(seqs)})
        w0 = eng._tick_dispatch_wall
        probs = eng.run(ids, wslots, rel, tables, np.zeros_like(rel),
                        lanes)                             # [B, V]
        self._record_block_spans(seqs, "ticked", ids.shape[0],
                                 [1] * len(seqs),
                                 eng._tick_dispatch_wall - w0)
        self._m_steps.inc()
        self._m_occupancy.observe(float(len(seqs)))
        self._m_tokens.inc(len(seqs), phase="decode")
        # bulk greedy argmax: one vectorized pass instead of a per-lane
        # python round-trip — this loop runs once per generated token
        # across the whole batch (identical result: argmax is invariant
        # under sample_token's monotone float64 cast)
        greedy = np.argmax(probs, axis=-1)
        for i, seq in enumerate(seqs):
            eng.lane_tables.advance(seq.lane, 1)
            self._emit_token(seq, probs[i], greedy_tok=int(greedy[i]))

    def _block_arrays(self, seqs: List[_Sequence], n_uniform: int):
        """Per-lane arrays for a fused/speculative block over a
        power-of-two bucket: pending token, view-relative position,
        active mask (padded lanes start retired), per-lane sampling
        config, and ``n_uniform`` host-drawn uniforms per sampled lane
        (from each request's seeded rng — per-request reproducibility is
        independent of batch composition)."""
        _, _, _, tables, lanes = self._compact(seqs, 1)
        b = len(lanes)
        arr = {
            "last": np.zeros(b, np.int32),
            "rel": np.zeros(b, np.int32),
            "active": np.zeros(b, bool),
            "eos": np.full(b, -1, np.int32),
            "temps": np.zeros(b, np.float32),
            "top_k": np.zeros(b, np.int32),
            "top_p": np.ones(b, np.float32),
            "u": np.zeros((b, n_uniform), np.float32),
            "tables": tables, "lanes": lanes,
        }
        for i, seq in enumerate(seqs):
            req = seq.req
            arr["last"][i] = seq.last_token
            arr["rel"][i] = self.engine.lane_tables.rel_pos(seq.lane)
            arr["active"][i] = True
            if req.eos_id is not None:
                arr["eos"][i] = req.eos_id
            if req.temperature > 0:
                arr["temps"][i] = req.temperature
                arr["top_k"][i] = req.top_k
                arr["top_p"][i] = req.top_p
                arr["u"][i] = req.rng.random(n_uniform)
        return arr

    def _fused_block_tick(self, seqs: List[_Sequence]) -> None:
        """One FUSED block: N device-resident decode steps, one
        dispatch, one host sync — retire/admit happen at this block
        boundary, finished lanes self-retired on device mid-block."""
        eng = self.engine
        n = eng.block_len
        budgets = []
        for seq in seqs:
            remaining = seq.req.max_new_tokens - len(seq.req.tokens)
            budgets.append(min(n, remaining))
            eng.lane_tables.ensure(seq.lane, budgets[-1])
        a = self._block_arrays(seqs, n)
        budget = np.zeros(a["last"].shape[0], np.int32)
        budget[:len(seqs)] = budgets
        _faults.check("serving.decode_step",
                      {"phase": "decode_block", "lanes": len(seqs),
                       "block_len": n})
        w0 = eng._tick_dispatch_wall
        toks, valid, n_emitted = eng.run_fused(
            a["last"], a["tables"], a["rel"], a["active"], budget,
            a["eos"], a["temps"], a["top_k"], a["top_p"], a["u"],
            a["lanes"])
        self._record_block_spans(
            seqs, "fused", a["last"].shape[0],
            [int(n_emitted[i]) for i in range(len(seqs))],
            eng._tick_dispatch_wall - w0)
        self._m_steps.inc()
        self._m_occupancy.observe(float(len(seqs)))
        emitted_total = 0
        for i, seq in enumerate(seqs):
            m = int(n_emitted[i])
            eng.lane_tables.advance(seq.lane, m)
            emitted_total += m
            self._deliver(seq, toks[i, :m])
        self._m_tokens.inc(emitted_total, phase="decode")
        _flight.record("decode_block", kind="fused", lanes=len(seqs),
                       block_len=n, tokens=emitted_total,
                       active=len(self._active))

    def _spec_block_tick(self, seqs: List[_Sequence]) -> None:
        """One SPECULATIVE block: the draft scans K+1 steps, the target
        verifies all K drafts in one batched chunk, accept/reject +
        bonus land on device — 1..K+1 tokens per lane for two dispatches
        and one host sync. EOS/max-tokens truncation of the valid prefix
        is host-side (the block boundary is already a host tick)."""
        eng = self.engine
        k = eng.draft_k
        # write budget = tokens the lane can still emit: slots past it
        # are masked on device, so a lane near max-tokens (or the
        # window edge) never draws pages — or worse, evicts live ones —
        # for positions that cannot exist
        wbudget = []
        for seq in seqs:
            remaining = seq.req.max_new_tokens - len(seq.req.tokens)
            wbudget.append(min(k + 1, remaining))
            eng.lane_tables.ensure(seq.lane, wbudget[-1])
        a = self._block_arrays(seqs, k + 1)
        n_sampled = int(np.count_nonzero(a["temps"] > 0))
        write_budget = np.zeros(a["last"].shape[0], np.int32)
        write_budget[:len(seqs)] = wbudget
        u_acc = np.zeros((a["last"].shape[0], k), np.float32)
        u_fix = np.zeros((a["last"].shape[0], k + 1), np.float32)
        for i, seq in enumerate(seqs):
            if seq.req.temperature > 0:
                u_acc[i] = seq.req.rng.random(k)
                u_fix[i] = seq.req.rng.random(k + 1)
        _faults.check("serving.decode_step",
                      {"phase": "spec_block", "lanes": len(seqs),
                       "draft_k": k})
        w0 = eng._tick_dispatch_wall
        d_toks, d_dists = eng.run_draft(
            a["last"], a["tables"], a["rel"], a["active"], write_budget,
            a["temps"], a["top_k"], a["top_p"], a["u"])
        emitted, valid, accepts = eng.run_verify(
            a["last"], a["tables"], a["rel"], a["active"], write_budget,
            d_toks, d_dists, a["temps"], a["top_k"], a["top_p"], u_acc,
            u_fix)
        spec_wall = eng._tick_dispatch_wall - w0
        self._m_steps.inc()
        self._m_occupancy.observe(float(len(seqs)))
        emitted_total = 0
        emitted_per_seq: List[int] = []
        for i, seq in enumerate(seqs):
            # ``valid`` is a prefix mask: its first False ends the run
            n_valid = (k + 1 if valid[i].all()
                       else int(np.argmin(valid[i])))
            m = self._deliver(seq, emitted[i, :n_valid])
            emitted_per_seq.append(m)
            if not seq.req.done:
                # a finished lane was already released by _deliver's
                # retire — advancing it would stamp a phantom position
                # onto a freed lane
                eng.lane_tables.advance(seq.lane, m)
            emitted_total += m
            # acceptance accounting over drafts that had a CHANCE of
            # being served (valid context within the write budget):
            # accepted = drafts that became output; rejected = chanced
            # drafts that went unserved — by target mismatch or because
            # the lane finished first (both are wasted draft work, which
            # is what the acceptance rate measures). Beyond-budget
            # drafts are garbage by construction and count as neither.
            chanced = min(k, wbudget[i])
            served = min(int(accepts[i]), m, chanced)
            self._m_draft.inc(served, result="accepted")
            self._m_draft.inc(chanced - served, result="rejected")
        self._record_block_spans(seqs, "speculative",
                                 a["last"].shape[0], emitted_per_seq,
                                 spec_wall)
        self._m_tokens.inc(emitted_total, phase="decode")
        _flight.record("decode_block", kind="speculative",
                       lanes=len(seqs), draft_k=k, tokens=emitted_total,
                       sampled_lanes=n_sampled, active=len(self._active))

    def _record_block_spans(self, seqs: List[_Sequence], kind: str,
                            bucket: int, tokens: List[int],
                            seconds: float) -> None:
        """Per-request child span for one decode/spec block dispatch —
        the request timeline's token-production record (lane, bucket,
        tokens emitted)."""
        if self.tracer is None:
            return
        for i, seq in enumerate(seqs):
            if seq.req.span is not None:
                self.tracer.record(
                    "decode_block", seconds, parent=seq.req.span,
                    attributes={"kind": kind, "lane": seq.lane,
                                "bucket": int(bucket),
                                "tokens": int(tokens[i]),
                                "state_layers": len(
                                    self.engine.state_layers)})

    def _emit_token(self, seq: _Sequence, probs: np.ndarray, *,
                    greedy_tok: Optional[int] = None) -> None:
        req = seq.req
        tok = (greedy_tok if greedy_tok is not None
               and req.temperature <= 0.0
               else _transformer.sample_token(probs, req.temperature,
                                              req.rng, top_k=req.top_k,
                                              top_p=req.top_p))
        self._deliver(seq, (tok,))

    def _deliver(self, seq: _Sequence, toks: Sequence[int]) -> int:
        """Hand one dispatch's generated tokens to the request
        (host-sampled by :meth:`_emit_token`, or device-sampled inside a
        fused/spec block): append up to the finishing token, stamp TTFT
        and the delivery, retire on EOS/max-tokens — the ONE copy of the
        finish rules, so device self-retire decisions and host
        bookkeeping cannot disagree. Returns how many were taken."""
        req = seq.req
        if len(toks) == 0:            # the lane emitted nothing this block
            return 0
        now = self.clock.monotonic()
        if req.t_first_token is None:
            self._stamp_first_token(seq, now)
        reason, taken = None, 0
        for tok in toks:
            req.tokens.append(int(tok))
            taken += 1
            if req.eos_id is not None and tok == req.eos_id:
                reason = "eos"
            elif len(req.tokens) >= req.max_new_tokens:
                reason = "max_tokens"
            if reason is not None:
                break
        seq.last_token = req.tokens[-1]
        if req.deliveries:
            self._m_gap.observe(now - req.deliveries[-1][0])
        req.deliveries.append((now, taken))
        if reason is not None:
            self._retire(seq, reason)
        return taken

    def _stamp_first_token(self, seq: _Sequence, now: float) -> None:
        req = seq.req
        req.t_first_token = now
        ttft = req.t_first_token - req.t_submit
        self._m_ttft.observe(ttft)
        # the decomposition SUMS to the measured TTFT: queue wait
        # (submit → admission) + this request's own prefill dispatch
        # wall + the compiles its ticks paid + everything else the
        # shared ticks did in between (other lanes' dispatches, host
        # bookkeeping). Components use the same clock as the TTFT
        # histogram, so the identity holds by construction.
        queue_wait = max(0.0, (req.t_admit if req.t_admit is not None
                               else req.t_submit) - req.t_submit)
        prefill = min(seq.prefill_s, max(0.0, ttft - queue_wait))
        compile_s = min(seq.compile_s,
                        max(0.0, ttft - queue_wait - prefill))
        req.ttft_breakdown = {
            "queue_wait": queue_wait, "prefill": prefill,
            "compile": compile_s,
            "dispatch": max(0.0, ttft - queue_wait - prefill
                            - compile_s)}
        for part, seconds in req.ttft_breakdown.items():
            self._m_ttft_part.observe(
                seconds, component=part,
                blocked_by=(req.blocked_by if part == "queue_wait"
                            else "none"))
        if req.span is not None:
            req.span.set_attribute("ttft_ms", round(ttft * 1000, 3))
            req.span.set_attribute(
                "ttft_breakdown_ms",
                {k: round(v * 1000, 3)
                 for k, v in req.ttft_breakdown.items()})

    def _retire(self, seq: _Sequence, reason: str) -> None:
        self.engine.release_lane(seq.lane)
        self._active.pop(seq.lane, None)
        self._finish(seq.req, reason)
        self._m_retired.inc(reason=reason)
        _flight.record("decode_retired", reason=reason, lane=seq.lane,
                       tokens=len(seq.req.tokens),
                       active=len(self._active))

    def _finish(self, req: DecodeRequest, reason: str) -> None:
        req.finish_reason = reason
        req.t_done = self.clock.monotonic()
        if req.t_first_token is not None and len(req.tokens) > 1:
            self._m_tpot.observe(
                (req.t_done - req.t_first_token)
                / (len(req.tokens) - 1))
        if req.tokens:
            self._m_goodput.inc(
                len(req.tokens),
                slo="met" if reason in ("eos", "max_tokens")
                else "missed")
        if req.span is not None:
            req.span.set_attribute("finish_reason", reason)
            req.span.set_attribute("tokens", len(req.tokens))
            self._end_request_spans(
                req, None if reason in ("eos", "max_tokens") else reason)
        req.event.set()

    # -- loop / lifecycle ---------------------------------------------

    def _loop(self) -> None:
        while not self._stopped:
            progressed = self.step_once()
            if progressed:
                # a tight loop re-takes the dispatch lock before a thread
                # parked on it (set_model's fence, the fleet heartbeat
                # probe) can wake: give up the GIL once per tick so a
                # busy replica cannot starve them for a whole decode
                time.sleep(0)
                continue
            with self._cond:
                if self._stopped:
                    break
                if not self._queue and not self._active:
                    with region("sched.wait_idle", self._m_wait,
                                why="idle"):
                        self._cond.wait(timeout=0.05)
                else:
                    # queued work that could not admit yet (page/lane
                    # pressure resolves at the next retirement)
                    with region("sched.wait_blocked", self._m_wait,
                                why="blocked"):
                        self._cond.wait(timeout=0.002)

    def active_count(self) -> int:
        return len(self._active)

    def queue_depth(self) -> int:
        return len(self._queue)

    @contextlib.contextmanager
    def fence(self):
        """Hold the scheduler at a step boundary (no dispatch in flight)
        and yield the number of in-flight sequences — the gate a model
        swap must pass through."""
        with self._dispatch_lock:
            yield len(self._active)

    def drain(self, timeout: float = 30.0) -> bool:
        """Decode-aware drain: stop ACCEPTING, keep SCHEDULING — every
        already-accepted request (queued or in flight) finishes, errors,
        or hits its own deadline before the drain reports clean. True if
        fully drained within ``timeout``. Threadless schedulers (tests)
        are stepped inline."""
        self._draining = True
        end = self.clock.monotonic() + timeout
        while self.clock.monotonic() < end:
            if not self._queue and not self._active:
                return True
            if self._thread is None:
                if not self.step_once():
                    self.clock.sleep(0.001)
            else:
                time.sleep(0.002)
        return not self._queue and not self._active

    def stop(self, timeout: float = 5.0) -> None:
        """Stop the loop; anything still queued or in flight finishes
        with ``finish_reason="shutdown"``. If the loop thread is wedged
        inside a hung dispatch (it holds the dispatch lock for the whole
        tick), the lock acquire below times out too and the stranded
        requests are still answered — engine bookkeeping is skipped in
        that case (the process is going down; waiters must not hang with
        it)."""
        self._draining = True
        self._stopped = True
        with self._cond:
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        fenced = self._dispatch_lock.acquire(timeout=max(0.1, timeout))
        try:
            for seq in list(self._active.values()):
                if fenced:
                    self.engine.release_lane(seq.lane)
                self._active.pop(seq.lane, None)
                self._finish(seq.req, "shutdown")
                self._m_retired.inc(reason="shutdown")
            with self._cond:
                queued, self._queue = list(self._queue), deque()
            for req in queued:
                self._finish(req, "shutdown")
                self._m_retired.inc(reason="shutdown")
        finally:
            if fenced:
                self._dispatch_lock.release()
