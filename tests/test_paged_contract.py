"""The one seam between the serving engine and what it serves: every layer
that holds decode state declares the dispatch arrays its ``apply_paged``
takes after its state (``paged_inputs``), what the arena holds for it
(``paged_entry``) and the shape of its paged read (``paged_read``). The
walker (``models.transformer.paged_decode_forward``), the arena
(``serving/kv_cache.PagedKVArena``) and the engine read those and name no
kind of layer.

The five kinds are held to the pools and reads the arena and the engine
built for them before the declarations existed (written out here as they
were), and a kind defined in this file, of one pool, runs through the
walker and the engine with no edit to either."""

import dataclasses
import inspect
from typing import Optional

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer as _transformer
from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.eva import EvaAttentionLayer
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.kda import KimiDeltaAttention
from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer, Layer,
                                               RnnOutputLayer)
from deeplearning4j_tpu.nn.conf.mla import MLAttentionLayer
from deeplearning4j_tpu.nn.conf.ssm import Mamba2Mixer
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine)
from deeplearning4j_tpu.serving.kv_cache import PagedEntry, PagedKVArena

PAGES, PS, LANES = 5, 4, 3
# what the walker hands a stateful vertex, by name (``tables`` is the
# attention layers' ``page_table`` parameter)
VIEW = {"tables", "write_slots", "rel_pos", "positions", "lane_ids", "valid",
        "fresh"}
PARAMETER = {"tables": "page_table"}


def built(n_in, layer):
    layer.set_n_in(InputType.recurrent(n_in))
    return layer


def paged(width, heads):
    """A K and a V pool of ``width`` a token, as the parent's arena built
    them for ``(heads, width / heads)``: float pools, or int8 codes with a
    scale a (page, head)."""
    def pools(dtype, int8):
        if int8:
            return [((PAGES, PS, width), jnp.int8, (PAGES, heads))] * 2
        return [((PAGES, PS, width), dtype)] * 2
    return pools


def lane_state(*shapes):
    """Per-lane state, float32 whatever the pools' dtype, never quantized."""
    def pools(dtype, int8):
        return [(shape, jnp.float32) for shape in shapes]
    return pools


def latent(width):
    """ONE pool, no second, and no int8 form (refused)."""
    def pools(dtype, int8):
        return None if int8 else [((PAGES, PS, width), dtype), None]
    return pools


# kind -> (the layer, the pools the parent's arena built, its read)
KINDS = {
    "self_attention": (
        lambda: built(20, SelfAttentionLayer(n_heads=4, n_kv_heads=2,
                                             head_size=12)),
        paged(24, 2), (2, 2, 12)),
    "latent_attention": (
        lambda: built(32, MLAttentionLayer(n_heads=4, kv_rank=32,
                                           rope_dim=8)),
        latent(128), (4, 1, 32)),
    "eva_attention": (
        lambda: built(64, EvaAttentionLayer(n_heads=4, head_dim=16,
                                            window_size=64, chunk_size=16)),
        paged(64, 4), (1, 4, 16)),
    "mamba2": (
        lambda: built(16, Mamba2Mixer(n_heads=2, head_dim=8, n_groups=1,
                                      state_size=4, conv_kernel=4)),
        lane_state((LANES, 3, 16 + 2 * 4), (LANES, 2, 8, 4)), None),
    "kimi_delta_attention": (
        lambda: built(16, KimiDeltaAttention(n_heads=2, head_dim=8,
                                             gate_rank=4, conv_kernel=4)),
        lane_state((LANES, 3, 48), (LANES, 2, 8, 8)), None),
}


def described(pool):
    if pool is None:
        return None
    if isinstance(pool, tuple):
        codes, scales = pool
        assert scales.dtype == jnp.float32
        return codes.shape, codes.dtype, scales.shape
    return pool.shape, pool.dtype


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_stateful_kind_declares_its_inputs_entry_and_read(kind):
    make, pools, read = KINDS[kind]
    layer = make()
    entry = layer.paged_entry(LANES)
    # its inputs are apply_paged's parameters after its state, one state
    # a pool of its entry
    n_state = len(entry.lane) or len(entry.rows)
    positional = [p.name for p in inspect.signature(
        layer.apply_paged).parameters.values()
        if p.kind is p.POSITIONAL_OR_KEYWORD]
    assert positional[2 + n_state:] == [
        PARAMETER.get(n, n) for n in layer.paged_inputs]
    assert set(layer.paged_inputs) <= VIEW
    # the pools the arena builds from the entry: the parent's, float and
    # int8 alike
    for dtype in (jnp.float32, jnp.bfloat16):
        arena = PagedKVArena({"v": entry}, num_pages=PAGES, page_size=PS,
                             dtype=dtype, with_allocator=False)
        got = [described(arena.k_pools[0]), described(arena.v_pools[0])]
        assert got == [None if p is None else tuple(p)
                       for p in pools(dtype, False)]
    want = pools(jnp.float32, True)
    if want is None:
        with pytest.raises(ValueError, match="a latent row has no heads"):
            PagedKVArena({"v": entry}, num_pages=PAGES, page_size=PS,
                         kv_dtype="int8", with_allocator=False)
    else:
        arena = PagedKVArena({"v": entry}, num_pages=PAGES, page_size=PS,
                             kv_dtype="int8", with_allocator=False)
        assert [described(arena.k_pools[0]),
                described(arena.v_pools[0])] == [tuple(p) for p in want]
    # the read the engine's accounting takes (None: no paged read)
    assert layer.paged_read == read
    assert (getattr(layer, "window_size", None) is not None) == bool(
        entry.window)


# -- a new kind, of one pool, with no edit to the walker or the engine -------

@dataclasses.dataclass
class RunningSum(Layer):
    """Out at position t: the sum of the inputs up to t. Its decode state
    is ONE array, a lane's running sum."""

    n_in: Optional[int] = None
    paged_inputs = ("lane_ids", "valid", "fresh")
    paged_read = None

    def set_n_in(self, input_type, override=False):
        if self.n_in is None or override:
            self.n_in = input_type.size

    def paged_entry(self, lanes):
        return PagedEntry(lane=((lanes, self.n_in),))

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        return jnp.cumsum(x, axis=1), state

    def apply_paged(self, params, x, sums, lane_ids, valid, fresh, *,
                    policy=None):
        rows = sums if lane_ids is None else jnp.take(
            sums, lane_ids, axis=0, mode="fill", fill_value=0)
        rows = jnp.where(fresh[:, None], 0.0, rows)
        out = rows[:, None, :] + jnp.cumsum(
            jnp.where(valid[:, :, None], x, 0.0), axis=1)
        new = rows + jnp.sum(jnp.where(valid[:, :, None], x, 0.0), axis=1)
        if lane_ids is None:
            return out, new
        return out, sums.at[lane_ids].set(new, mode="drop")


V, D = 11, 6


@pytest.fixture(scope="module")
def toy():
    conf = (NeuralNetConfiguration.builder().seed(3).graph_builder()
            .add_inputs("in")
            .add_layer("embed", EmbeddingSequenceLayer(
                n_in=V, n_out=D, activation="identity"), "in")
            .add_layer("sum", RunningSum(), "embed")
            .add_layer("out", RnnOutputLayer(
                n_in=D, n_out=V, activation="softmax",
                loss="sparse_mcxent"), "sum")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(1))
            .build())
    return ComputationGraph(conf).init()


def greedy(net, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        probs = net.output(np.asarray(ids, np.int32)[None, :, None])
        ids.append(int(np.argmax(np.asarray(probs)[0, -1])))
    return ids[len(prompt):]


def test_a_kind_of_one_pool_runs_through_the_walker_and_the_engine(toy):
    assert _transformer.stateful_vertices(toy) == ["sum"]
    assert _transformer.stateful_vertices(toy, "lane_ids") == ["sum"]
    assert _transformer.attention_vertices(toy) == []
    ids = np.random.default_rng(4).integers(0, V, (2, 9)).astype(np.int32)
    dense = np.asarray(toy.output(ids[:, :, None]))
    # the walker, in two chunks, the state carried between them in one pool
    pools = [jnp.zeros((2, D), jnp.float32)]
    got = []
    for lo, hi in ((0, 5), (5, 9)):
        rel = np.full(2, lo, np.int32)
        slots = np.tile(np.arange(lo, hi, dtype=np.int32), (2, 1))
        probs, pools, v_pools = _transformer.paged_decode_forward(
            toy, toy.params, pools, [None], ids[:, lo:hi],
            np.zeros((2, 1), np.int32), slots, rel,
            lane_ids=np.arange(2, dtype=np.int32))
        assert v_pools == [None]
        got.append(np.asarray(probs))
    np.testing.assert_allclose(np.concatenate(got, 1), dense, atol=1e-6)
    # the engine: a ticked and a fused decode, the same greedy tokens
    prompt = ids[0, :6]
    want = greedy(toy, prompt, 6)
    for block_len in (1, 4):
        eng = PagedDecodeEngine(toy, max_batch=2, page_size=4,
                                pages_per_seq=4, prefill_chunk=8,
                                block_len=block_len)
        assert eng.state_layers == ["sum"] and eng.arena.v_pools == [None]
        sched = DecodeScheduler(eng, start_thread=False)
        reqs = [sched.submit(prompt, 6), sched.submit(ids[1, :3], 5)]
        for _ in range(40):
            if all(r.done for r in reqs):
                break
            sched.step_once()
        assert reqs[0].tokens == want
        assert reqs[1].tokens == greedy(toy, ids[1, :3], 5)
