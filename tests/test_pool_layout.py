"""The stored shape of a K/V pool, ``[num_pages, page_size, h*d]``
(``ops/paged_attention``, "Layout conventions"), held three ways:

- every program the decode engine dispatches touches a whole pool only
  as a scatter's operand and result, a gather's operand, a loop carry or
  a call's argument (its jaxpr, on the CPU);
- compiled for a described TPU v5e, the prefill and the fused program
  hold no copy and no convert whose shape is a pool's, and the read at
  each serving cell's decode shape carries its pools in float32, and the
  latent cell's largest prefill and fused programs fit the chip with
  their read's trips, and the read over one K/V head relays neither its
  chunks nor its accumulator while the reads of several still turn
  their chunks (skipped where no TPU topology can be described);
- ``paged_write``, ``paged_gather`` and ``paged_read_attention`` give,
  bit for bit, what the ``[num_pages, page_size, h, d]`` formulation they
  replaced gave (kept below as the reference; the gather's result has
  the keys on the minor axis now, so it is compared turned).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer as _transformer
from deeplearning4j_tpu.models.transformer import (draft_transformer_lm,
                                                   transformer_lm)
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.ops import paged_attention as _paged
from deeplearning4j_tpu.ops.paged_attention import (paged_gather,
                                                    paged_read_attention,
                                                    paged_write)
from deeplearning4j_tpu.serving.decode import PagedDecodeEngine
from deeplearning4j_tpu.serving.kv_cache import PagedKVArena
from test_paged_read import as_pool, primitives, quantized

# ---------------------------------------------------------------------------
# 1. the programs' jaxprs: a pool is scattered into, gathered from, carried
# ---------------------------------------------------------------------------

VOCAB = 48
NUM_PAGES = 37          # no other array of a program has 37 rows
KINDS = {"prefill_chunk": "paged_decode[S1xT8x", "ticked_step":
         "paged_decode[S1xT1x", "fused_block": "fused_decode[",
         "draft_loop": "spec_draft[", "verify": "spec_verify["}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def pool_faults(jaxpr, pools):
    """Every use of a pool-shaped value that the layout rule forbids, as
    text. ``pools`` is the set of ``(shape, dtype)`` a pool may have."""
    def is_pool(v):
        aval = getattr(v, "aval", None)
        return (aval is not None and hasattr(aval, "shape")
                and (tuple(aval.shape), str(aval.dtype)) in pools)

    faults = []
    for eqn in jaxpr.eqns:
        subs = list(_sub_jaxprs(eqn))
        for sub in subs:
            faults += pool_faults(sub, pools)
        if subs:                    # a loop, a branch or a call carries it
            continue
        name = eqn.primitive.name
        ins = [i for i, v in enumerate(eqn.invars) if is_pool(v)]
        outs = [i for i, v in enumerate(eqn.outvars) if is_pool(v)]
        allowed_in = {"scatter": [0], "gather": [0]}.get(name, [])
        allowed_out = [0] if name == "scatter" else []
        if any(i not in allowed_in for i in ins) or any(
                i not in allowed_out for i in outs):
            faults.append(f"{name}: pool-shaped operands {ins}, "
                          f"results {outs}")
    return faults


def _nets(dtype):
    net = ComputationGraph(transformer_lm(
        VOCAB, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=3,
        input_ids=True, dtype=dtype)).init()
    # the draft stays float32: its one-row loop would not round anyway,
    # and under ``mixed_bf16`` ``draft_decode_loop`` does not trace (its
    # greedy branch returns the row's dtype, the sampled one float32)
    draft = ComputationGraph(draft_transformer_lm(
        VOCAB, d_model=8, n_heads=2, d_ff=16, seed=5)).init()
    return net, draft


def _recorded_programs(kv_dtype, nets, monkeypatch):
    """name -> (jaxpr, pool shapes) of every program the engine's warm-up
    dispatches, in fused mode and in speculative mode."""
    net, draft = nets
    seen = {}
    real = PagedDecodeEngine._dispatch

    def recording(self, name, step, arena, params, args, **kw):
        if name not in seen:
            pools = {(tuple(x.shape), str(x.dtype))
                     for p in arena.k_pools
                     for x in ([p[0]] if isinstance(p, tuple) else [p])}
            seen[name] = (jax.make_jaxpr(step)(
                params, arena.k_pools, arena.v_pools, *args).jaxpr, pools)
        return real(self, name, step, arena, params, args, **kw)

    monkeypatch.setattr(PagedDecodeEngine, "_dispatch", recording)
    common = {"max_batch": 1, "page_size": 4, "pages_per_seq": 8,
              "num_pages": NUM_PAGES, "prefill_chunk": 8,
              "prefix_cache": True, "kv_dtype": kv_dtype}
    PagedDecodeEngine(net, block_len=4, **common).warmup()
    PagedDecodeEngine(net, block_len=1, draft_net=draft, draft_k=2,
                      **common).warmup()
    return seen


# bf16: a bfloat16 query over float32 pools, where the read rounds the
# chunks it gathers (and nothing else: not the pool)
@pytest.fixture(scope="module", params=["f32", "int8", "bf16"])
def programs(request):
    nets = _nets("mixed_bf16" if request.param == "bf16" else "float32")
    with pytest.MonkeyPatch.context() as mp:
        return request.param, _recorded_programs(
            "int8" if request.param == "int8" else None, nets, mp)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_no_program_works_on_a_whole_pool(programs, kind):
    """In the program's jaxpr a pool-shaped value is an input, a scatter's
    operand or result, a gather's operand or a loop's carry: no reshape,
    transpose, convert or arithmetic has one as operand or result. The
    rounding of the read (several query rows under ``mixed_bf16``: the
    prefill chunk and the verify chunk) is in the program and is none of
    those: its operand is the gathered chunk."""
    policy, programs = programs
    found = [n for n in programs if n.startswith(KINDS[kind])]
    assert found, (kind, sorted(programs))
    for name in found:
        jaxpr, pools = programs[name]
        assert len(pools) == 1 and all(len(s) == 3 and s[0] == NUM_PAGES
                                       for s, _ in pools)
        assert any(tuple(v.aval.shape) == s for v in jaxpr.invars
                   for s, _ in pools)       # the walker sees the pools
        assert pool_faults(jaxpr, pools) == [], name
        assert ("reduce_precision" in set(primitives(jaxpr))) == (
            policy == "bf16" and kind in ("prefill_chunk", "verify")), name


def _viewed(pool, table):          # what ISSUE 32 warns of
    return jnp.take(pool.reshape(NUM_PAGES, 4, 2, 8), table, axis=0)


def _rounded_whole(pool, table):    # what the compiler did until PR 38
    return jnp.take(jax.lax.reduce_precision(pool, 8, 7), table, axis=0)


def _rounded_chunk(pool, table):    # what the read does
    return jax.lax.reduce_precision(jnp.take(pool, table, axis=0), 8, 7)


@pytest.mark.parametrize("fn,fault", [
    (_viewed, "reshape"), (_rounded_whole, "reduce_precision"),
    (_rounded_chunk, None)])
def test_the_walker_finds_a_pool_worked_on(fn, fault):
    """The guard guards: a view of the pool around a 4-D primitive and a
    rounding of the whole pool are reported; a rounding of the gathered
    pages, the read's, is none of its business."""
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((NUM_PAGES, 4, 16), jnp.float32),
                               jnp.zeros((1, 2), jnp.int32)).jaxpr
    faults = pool_faults(jaxpr, {((NUM_PAGES, 4, 16), "float32")})
    assert [f.split(":")[0] for f in faults] == ([fault] if fault else [])


# ---------------------------------------------------------------------------
# 2. compiled for a described v5e: no copy and no rounding of a pool (this
#    file alone describes a topology: one worker loads the TPU's library)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _described(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _pool_shaped(text, pool_shape, ops):
    """``(dtype, op)`` of every instruction of ``ops`` in the compiled
    text whose result has a pool's shape."""
    shape = ",".join(str(n) for n in pool_shape)
    return re.findall(r"= (\w+)\[" + shape + r"\]\{[^}]*\} ("
                      + "|".join(map(re.escape, ops)) + r")\(", text)


@pytest.mark.parametrize("program", ["prefill", "fused"])
def test_compiled_for_v5e_no_program_copies_a_pool(one_chip, program):
    """Heads of 64 (half a 128-lane tile) and 224 pages, as in the
    benchmark's OPT: stored ``[.., h, d]``, each of the two programs
    relaid every donated pool at entry and before its result (8 copies
    for these 4 pools; 40 pages show none, so the sizes matter). Nor a
    ``convert``: until PR 38 the prefill program's text held four
    ``bf16[224,16,256] convert``, the compiler's rounding of each whole
    float32 pool for the read's matrix products, and its read loop
    carried the bfloat16 copies (``ops/paged_attention``, module
    docstring)."""
    lanes, chunk, block, page_size, pages_per_seq = 2, 128, 4, 16, 16
    conf = transformer_lm(512, n_layers=2, d_model=256, n_heads=4,
                          d_ff=512, seed=3, input_ids=True,
                          dtype="mixed_bf16")
    net = ComputationGraph(conf)
    params = _described(
        jax.eval_shape(lambda: ComputationGraph(conf).init().params),
        one_chip)
    dims = {n: net._vertex_layer(n).paged_entry(lanes)
            for n in _transformer.attention_vertices(net)}
    k, v = _described(jax.eval_shape(lambda: (lambda a: (
        a.k_pools, a.v_pools))(PagedKVArena(
            dims, num_pages=224, page_size=page_size,
            with_allocator=False))), one_chip)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tables, i32 = arg((lanes, pages_per_seq), jnp.int32), jnp.int32
    if program == "prefill":
        def step(params, k, v, *a):             # the last: the rows named
            return _transformer.paged_decode_forward(
                net, params, k, v, *a[:-1], out_rows=a[-1])
        args = (arg((lanes, chunk), i32), tables, arg((lanes, chunk), i32),
                arg((lanes,), i32), arg((lanes,), i32))
    else:
        def step(params, k, v, *a):
            return _transformer.fused_decode_loop(net, params, k, v, *a)
        lane = lambda dt: arg((lanes,), dt)                 # noqa: E731
        args = (lane(i32), tables, lane(i32), lane(bool), lane(i32),
                lane(i32), lane(jnp.float32), lane(i32), lane(jnp.float32),
                arg((lanes, block), jnp.float32))
    text = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k, v, *args).compile().as_text()
    assert "scatter" in text and "gather" in text
    assert _pool_shaped(text, k[0].shape, ["copy", "convert"]) == []


# the paged write and read of one attention vertex at each serving cell's
# decode shape: lanes, query rows a K/V head, K/V heads, head size, pages,
# pages a lane, and the latent read's value width (`benchmarks/configs`)
READ_SHAPES = {
    # 128 heads on the one latent row of 576 numbers in 640 columns
    "latent": (32, 128, 1, 640, 12288, 512, 512),
    # the same in the 32-lane prefill chunk: 128 positions x 128 heads
    "latent_prefill": (32, 128 * 128, 1, 640, 12288, 512, 512),
    # the hybrid's grouped-query layer: 16 heads on each of 2 K/V heads
    "hybrid": (32, 16, 2, 128, 4096, 128, None),
    # OPT's fused block and ticked step: one row a head
    "opt_one_row": (8, 1, 32, 64, 224, 128, None),
}
PAGE_SIZE = 16


@pytest.fixture(scope="module")
def read_text(one_chip):
    """cell -> the compiled text of the read at ``READ_SHAPES[cell]``
    (a bfloat16 query over float32 pools), compiled once a module."""
    texts = {}

    def text(cell):
        if cell not in texts:
            texts[cell] = _compiled_read(one_chip, *READ_SHAPES[cell])
        return texts[cell]
    return text


def _compiled_read(one_chip, lanes, rows, h, d, num_pages, pages_per_seq,
                   v_width):
    group = rows if rows > 1 else 1
    latent = v_width is not None

    def step(k_pool, v_pool, q, new, table, slots, rel):
        k_pool = paged_write(k_pool, new, table, slots)
        if not latent:
            v_pool = paged_write(v_pool, new, table, slots)
        out = paged_read_attention(
            q, k_pool, None if latent else v_pool, table, rel,
            jnp.asarray(0.125, q.dtype), group=group, v_width=v_width)
        return out, k_pool, v_pool

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((num_pages, PAGE_SIZE, h * d), jnp.float32)
    with jax.enable_x64(False):     # as on the chip; the suite runs with x64
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            pool, pool, arg((lanes, rows, h, d), jnp.bfloat16),
            arg((lanes, 1, h, d), jnp.bfloat16),
            arg((lanes, pages_per_seq), jnp.int32),
            arg((lanes, 1), jnp.int32),
            arg((lanes,), jnp.int32)).compile().as_text()


@pytest.mark.parametrize("cell", sorted(READ_SHAPES))
def test_compiled_for_v5e_the_read_rounds_no_pool(read_text, cell):
    """A bfloat16 query over float32 pools: where the read's products
    are matrix products (more than one row a K/V head) the compiler
    rounded each WHOLE pool to bfloat16 before the read loop (one
    ``bf16[12288,16,640] convert`` at the latent shape, two
    ``bf16[4096,16,256]`` and two pool-shaped ``copy-done`` at the
    hybrid's; none with one row, where the products are the vector
    unit's, in float32). The read rounds the gathered chunk itself, so
    no pool-shaped ``convert``, ``copy`` or ``bitcast-convert`` is left
    and the read's ``while`` carries every pool in float32."""
    _, _, h, d, num_pages, _, v_width = READ_SHAPES[cell]
    latent = v_width is not None
    pool_shape = (num_pages, PAGE_SIZE, h * d)
    text = read_text(cell)
    assert "scatter" in text and "gather" in text
    assert _pool_shaped(text, pool_shape, [
        "convert", "copy", "bitcast-convert", "copy-done"]) == []
    shape = ",".join(str(n) for n in pool_shape)
    carried = [dt for carry in re.findall(r"= (\(.*?\)) while\(", text)
               for dt in re.findall(r"(\w+)\[" + shape + r"\]", carry)]
    assert carried == ["f32"] * (1 if latent else 2)


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]\{([\d,]*)")


def _arrays(type_text):
    """``(dtype, dims, minor_to_major)`` of each array in an HLO type."""
    return [(dt, tuple(int(n) for n in dims.split(",") if n),
             tuple(int(n) for n in layout.split(",") if n))
            for dt, dims, layout in _ARRAY.findall(type_text)]


def _computations(text):
    """name -> {instruction -> (type text, opcode, operands text)}."""
    comps, body = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head:
            body = comps.setdefault(head.group(1), {})
        elif body is not None and (m := _INSTR.match(line)):
            body[m.group(1)] = m.group(2, 3, 4)
    return comps


def _loop_bodies(comps):
    """The computations a ``while`` runs, nested loops included."""
    bodies = set()
    for instrs in comps.values():
        for _, op, rest in instrs.values():
            if op == "while":
                bodies.update(re.findall(r"body=%([\w.\-]+)", rest))
    return bodies


def _same_dims(dims, want):
    return (sorted(n for n in dims if n != 1)
            == sorted(n for n in want if n != 1))


def _relayouts(comps, names, want):
    """Every ``copy`` and ``copy-start`` in computations ``names`` whose
    result has the dims ``want`` (in any order, unit axes aside) and a
    layout other than its operand's. A ``copy-start`` whose two buffers
    differ only in memory space moves an array between HBM and the chip's
    fast memory and turns nothing."""
    found = []
    for name in names:
        instrs = comps[name]
        for dest, (type_text, op, rest) in instrs.items():
            if op not in ("copy", "copy-start"):
                continue
            arrays = _arrays(type_text)
            if not arrays or not _same_dims(arrays[0][1], want):
                continue
            if op == "copy-start":
                src = arrays[1] if len(arrays) > 1 else None
            else:
                operand = re.match(r"%([\w.\-]+)", rest)
                src = (_arrays(instrs[operand.group(1)][0]) or [None])[0] \
                    if operand and operand.group(1) in instrs else None
            if src is None or src[1:] != arrays[0][1:]:
                found.append(f"{name}: {dest} = {type_text} {op}")
    return found


@pytest.mark.parametrize("cell", sorted(READ_SHAPES))
def test_compiled_for_v5e_a_one_head_read_turns_nothing(read_text, cell):
    """The latent read (one K/V head) folds each chunk as it was
    gathered: in the read loop no chunk ``[S, keys, row]`` is relaid (the
    turned read copied ``f32[32,512,640]`` on every trip of the fused
    block, ``f32[32,256,640]`` in the 32-lane prefill), and no copy of the
    accumulator ``[S, rows, d_v]`` follows the loop (``f32[32,128,1,512]``,
    and 1 GiB, ``f32[32,16384,1,512]``, in the prefill). The reads of
    several heads still turn their chunks (two ``bf16[32,128,256]`` in
    the hybrid's loop, two ``f32[8,2048,128]`` in OPT's): the form follows
    the read's K/V heads and nothing else."""
    lanes, rows, h, d, _, pages_per_seq, v_width = READ_SHAPES[cell]
    d_v = d if v_width is None else v_width
    keys = PAGE_SIZE * _paged.read_chunk_pages(PAGE_SIZE, pages_per_seq, d_v,
                                               lanes * h * rows)
    comps = _computations(read_text(cell))
    turns = _relayouts(comps, _loop_bodies(comps), (lanes, keys, h * d))
    acc = _relayouts(comps, comps, (lanes, rows, h, d_v))
    if h == 1:
        assert turns == [] and acc == [], (turns, acc)
    else:
        assert len(turns) >= 2, turns


# the latent cell's largest buckets (`benchmarks/configs/openpangu-ultra-
# moe-718b.json`): program, lanes, tokens a lane (a prefill chunk; a fused
# block's steps), the read's float32 logit cap (None: the program's own)
LATENT_BUCKETS = {
    "prefill_S32xT128": ("prefill", 32, 128, None),
    "fused_S32xN8": ("fused", 32, 8, None),
    # the same prefill with the read's guard lifted: 512-key trips
    "prefill_S32xT128_unguarded": ("prefill", 32, 128, 1 << 40),
}
LATENT_PEAK_GIB = 14.9


@pytest.mark.parametrize("bucket", sorted(LATENT_BUCKETS))
def test_compiled_for_v5e_the_latent_programs_fit(one_chip, bucket, request,
                                                  monkeypatch):
    """The latent cell's whole 5-layer programs at its sizes (weights in
    bfloat16, five float32 latent pools of 12,288 pages, 32 lanes over
    512 pages; the expert layers' Pallas kernel compiled, as on the chip):
    arguments plus temporaries stay at or under 14.9 GiB of the chip's
    15.75. A trip that follows the value width (512 keys) widens the
    trip's logits to 1 GiB in the 32-lane prefill chunk, whose peak then
    reads 15.00 GiB (13.58 with 128-key trips; TPU compiler here, PR 40):
    the guard halves that bucket's trip (256 keys: 13.96 GiB, temporaries
    2.45 GiB; the fused block's 0.53)."""
    from test_pangu import ROOT, common
    import json
    import os
    from deeplearning4j_tpu.util import xla as _xla
    kind, lanes, t, cap = LATENT_BUCKETS[bucket]
    monkeypatch.setattr(_xla, "kernel_mode", lambda: "mosaic")
    if cap is not None:             # a trace of its own, and none left
        paged_read_attention.clear_cache()
        monkeypatch.setattr(_paged, "READ_TRIP_LOGIT_BYTES", cap)
        request.addfinalizer(paged_read_attention.clear_cache)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "openpangu-ultra-moe-718b.json")) as f:
        cfg = json.load(f)
    eng = cfg["engine"]
    pps, ps = eng["pages_per_seq"], eng["page_size"]
    conf = common.load_family(cfg).build_conf(cfg, 0, max_cache_t=pps * ps)
    net = ComputationGraph(conf)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, lane = jnp.int32, lambda dt: arg((lanes,), dt)      # noqa: E731
    with jax.enable_x64(False):     # as on the chip; the suite runs with x64
        params = jax.tree_util.tree_map(
            lambda a: arg(a.shape, jnp.bfloat16 if a.dtype == jnp.float32
                          else a.dtype),
            jax.eval_shape(lambda: ComputationGraph(conf).init().params))
        pools = [arg((eng["num_pages"], ps,
                      net._vertex_layer(v).pool_width), jnp.float32)
                 for v in _transformer.attention_vertices(net)]
        if kind == "prefill":
            def step(params, k, v, ids, tables, ws, rel, rows, pos, fed):
                return _transformer.paged_decode_forward(
                    net, params, k, v, ids, tables, ws, rel, counts=[],
                    out_rows=rows, positions=pos, fed=fed)
            args = (arg((lanes, t), i32), arg((lanes, pps), i32),
                    arg((lanes, t), i32), lane(i32), lane(i32), lane(i32),
                    lane(i32))
        else:
            def step(params, k, v, *a):
                *a, pos = a
                return _transformer.fused_decode_loop(net, params, k, v, *a,
                                                      positions=pos)
            args = (lane(i32), arg((lanes, pps), i32), lane(i32),
                    lane(bool), lane(i32), lane(i32), lane(jnp.float32),
                    lane(i32), lane(jnp.float32),
                    arg((lanes, t), jnp.float32), lane(i32))
        mem = jax.jit(step, donate_argnums=(1, 2)).lower(
            params, pools, [None] * len(pools), *args).compile(
            ).memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    if cap is None:
        assert peak <= LATENT_PEAK_GIB, (bucket, peak)
    else:
        assert peak > LATENT_PEAK_GIB, (bucket, peak)


# the hybrid of KDA and gated GQA (`benchmarks/configs/solar-open2-250b.json`):
# its largest buckets, at most 14.5 GiB each
SOLAR_BUCKETS = {"prefill_S32xT128": ("prefill", 32, 128),
                 "fused_S32xN8": ("fused", 32, 8)}
SOLAR_PEAK_GIB = 14.5


@pytest.mark.parametrize("bucket", sorted(SOLAR_BUCKETS))
def test_compiled_for_v5e_the_solar_programs_fit(one_chip, bucket,
                                                 monkeypatch):
    """The 4-layer programs at the cell's sizes (weights in bfloat16, the
    GQA layer's float32 pools of 28,672 pages, three KDA layers' per-lane
    state of 32 lanes; the expert layers' gated kernel compiled, as on the
    chip, blocked over 2048 of the hidden 4096 and 256 of the experts'
    1280: ``gated_chunks``): arguments plus temporaries stay at or under
    14.5 GiB. With 32,768 pages the 32-lane prefill program read 14.87
    (its temporaries 4.31 GiB, mostly the KDA layers' float32 convolution
    over 32 x 128 x 24,576 and the expert layers' pair-level rows),
    hence 28,672 (14.37); the fused block 11.25."""
    from test_pangu import ROOT, common
    import json
    import os
    from deeplearning4j_tpu.util import xla as _xla
    kind, lanes, t = SOLAR_BUCKETS[bucket]
    monkeypatch.setattr(_xla, "kernel_mode", lambda: "mosaic")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "solar-open2-250b.json")) as f:
        cfg = json.load(f)
    eng = cfg["engine"]
    pps, ps = eng["pages_per_seq"], eng["page_size"]
    conf = common.load_family(cfg).build_conf(cfg, 0, max_cache_t=pps * ps)
    net = ComputationGraph(conf)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, lane = jnp.int32, lambda dt: arg((lanes,), dt)      # noqa: E731
    with jax.enable_x64(False):     # as on the chip; the suite runs with x64
        params = jax.tree_util.tree_map(
            lambda a: arg(a.shape, jnp.bfloat16 if a.dtype == jnp.float32
                          else a.dtype),
            jax.eval_shape(lambda: ComputationGraph(conf).init().params))
        k, v = [], []
        for name in _transformer.stateful_vertices(net):
            layer = net._vertex_layer(name)
            if name in _transformer.kda_vertices(net):
                shapes = layer.state_shapes(eng["max_batch"])
            else:
                shapes = [(eng["num_pages"], ps, layer.kv_width)] * 2
            k.append(arg(shapes[0], jnp.float32))
            v.append(arg(shapes[1], jnp.float32))
        if kind == "prefill":
            def step(params, k, v, ids, tables, ws, rel, rows, lane_ids):
                return _transformer.paged_decode_forward(
                    net, params, k, v, ids, tables, ws, rel, counts=[],
                    out_rows=rows, lane_ids=lane_ids)
            args = (arg((lanes, t), i32), arg((lanes, pps), i32),
                    arg((lanes, t), i32), lane(i32), lane(i32), lane(i32))
        else:
            def step(params, k, v, *a):
                *a, lane_ids = a
                return _transformer.fused_decode_loop(net, params, k, v, *a,
                                                      lane_ids=lane_ids)
            args = (lane(i32), arg((lanes, pps), i32), lane(i32),
                    lane(bool), lane(i32), lane(i32), lane(jnp.float32),
                    lane(i32), lane(jnp.float32),
                    arg((lanes, t), jnp.float32), lane(i32))
        compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
            params, k, v, *args).compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    assert peak <= SOLAR_PEAK_GIB, (bucket, peak)
    assert compiled.as_text().count("moe_gated_ffn") >= 4


# the expert kernels at the cells' shapes, (tiles, rows, width, expert
# width, experts held, stacks): the latent decode step (32 lanes x 8 pairs
# in tiles of 2 rows over 16 held: 16 + 128 tiles), Solar's 32-lane
# prefill (32 x 128 x 8 pairs in tiles of 128 over 40 held: 40 + 256), and
# the hybrid's squared-ReLU decode step (32 x 22 pairs, 128 held: 480)
EXPERT_KERNELS = {"gated_latent_decode": (144, 2, 7680, 2048, 16, 3),
                  "gated_solar_prefill": (296, 128, 4096, 1280, 40, 3),
                  "relu2_hybrid_decode": (480, 2, 1024, 2688, 128, 2)}


def _pallas_grids(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["grid_mapping"]
        for sub in _sub_jaxprs(eqn):
            yield from _pallas_grids(sub)


@pytest.mark.parametrize("kernel", sorted(EXPERT_KERNELS))
def test_compiled_for_v5e_the_gated_kernel_walks_a_dynamic_tile_axis(
        one_chip, kernel):
    """The gated kernel's tile axis is a bound computed on the device
    (the live tiles), its two chunk axes static, and it compiles so; the
    squared-ReLU kernel's grid stays all static, every tile it is sized
    for."""
    from deeplearning4j_tpu.ops import grouped_ffn
    tiles, rows, d, f, e, n_stacks = EXPERT_KERNELS[kernel]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    stacks = [arg((e, d, f)), arg((e, f, d))]
    if n_stacks == 3:
        stacks.insert(0, arg((e, d, f)))
        dc, fc = grouped_ffn.gated_chunks(d, f)
        static = (f // fc, 2 * d // dc)
    else:
        static = (tiles, f // grouped_ffn.f_chunk(f))
    args = (arg((tiles, rows, d)), arg((tiles,), jnp.int32),
            arg((), jnp.int32), *stacks)

    def call(x, tile_e, n_tiles, *stacks):
        return grouped_ffn.tile_ffn_pallas(x, tile_e, n_tiles, *stacks)

    with jax.enable_x64(False):     # as on the chip; the suite runs with x64
        (grid,) = _pallas_grids(jax.make_jaxpr(call)(*args).jaxpr)
        text = jax.jit(call).lower(*args).compile().as_text()
    if n_stacks == 3:
        assert grid.num_dynamic_grid_bounds == 1
        assert not isinstance(grid.grid[0], int)
        assert tuple(grid.grid[1:]) == static
    else:
        assert grid.num_dynamic_grid_bounds == 0
        assert tuple(grid.grid) == static
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("bh, t, d, calls", [
    (32, 8192, 64, 1),       # the training cell: [1, 8192, 32, 64]
    (4, 16384, 64, 1),       # a head's float32 dq at the budget, 4 MiB
    (4, 8192, 128, 1),
    (4, 32768, 64, 2),       # above it: the dq pass and the dk/dv pass
])
def test_compiled_for_v5e_the_flash_backward_fits_vmem(one_chip, bh, t, d,
                                                       calls):
    """Kept beside the pools' compiles because one file alone may
    describe a topology. The one-call backward holds a head's whole dq in
    a VMEM scratch beside a resident output block: the TPU compiler has to
    take its tiles (``_bwd_tiles``) under the kernel's own limit, at the
    cell's shape and at the budget's edge, and a shape above the budget
    has to lower to the two-call backward."""
    from deeplearning4j_tpu.ops import flash_attention as fa
    bq, bk = fa._bwd_tiles(t, None, pallas=True)

    def bwd(q, k, v, mask, out, lse, dout):
        return fa._flash_bwd_btd_pallas(
            q, k, v, mask, out, lse, dout, scale=d ** -0.5, causal=True,
            block_q=bq, block_k=bk, interpret=False, n_heads=bh)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    a = arg((bh, t, d))
    with jax.enable_x64(False):     # as on the chip; the suite runs with x64
        text = jax.jit(bwd).lower(
            a, a, a, arg((1, t), jnp.float32), a, arg((bh, t), jnp.float32),
            a).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == calls


# ---------------------------------------------------------------------------
# 3. equal, bit for bit, to the [num_pages, page_size, h, d] formulation
# ---------------------------------------------------------------------------

H, D, PAGE, PAGES_PER_SEQ, LANES = 2, 8, 16, 20, 3


def _targets_4d(pool4, page_table, write_slots):
    num_pages, page_size = pool4.shape[0], pool4.shape[1]
    p_idx = jnp.clip(write_slots // page_size, 0, page_table.shape[1] - 1)
    phys = jnp.take_along_axis(page_table, p_idx, axis=1)
    return (jnp.where(write_slots >= 0, phys, num_pages),
            write_slots % page_size)


def write_4d(pool4, new, page_table, write_slots):
    """``paged_write`` and ``_paged_write_q8`` as they stood before PR 32."""
    if not isinstance(pool4, tuple):
        phys, off = _targets_4d(pool4, page_table, write_slots)
        return pool4.at[phys, off].set(new.astype(pool4.dtype), mode="drop")
    q, scales = pool4
    num_pages, h = q.shape[0], q.shape[2]
    phys, off = _targets_4d(q, page_table, write_slots)
    newf = new.astype(jnp.float32)
    amax_tok = jnp.max(jnp.abs(newf), axis=-1)
    flat_phys = phys.reshape(-1)
    amax_page = (jnp.zeros((num_pages, h), jnp.float32)
                 .at[flat_phys].max(amax_tok.reshape(-1, h), mode="drop"))
    new_scales = jnp.maximum(scales, amax_page / 127.0)
    ratio = jnp.where(new_scales > 0, scales / new_scales, 0.0)
    pages_q = jnp.take(q, flat_phys, axis=0, mode="fill", fill_value=0)
    r = jnp.take(ratio, flat_phys, axis=0,
                 mode="fill", fill_value=0.0)[:, None, :, None]
    q = q.at[flat_phys].set(
        jnp.round(pages_q.astype(jnp.float32) * r).astype(jnp.int8),
        mode="drop")
    s_tok = jnp.take(new_scales, phys, axis=0, mode="fill", fill_value=0.0)
    rows = jnp.round(newf / jnp.maximum(s_tok[..., None], 1e-30))
    rows = jnp.clip(rows, -127, 127).astype(jnp.int8)
    return (q.at[phys, off].set(rows, mode="drop"), new_scales)


def gather_4d(pool4, page_table):
    """``paged_gather`` as it stood before PR 32: ``[S, keys, h, d]``."""
    if isinstance(pool4, tuple):
        q, scales = pool4
        g = jnp.take(q, page_table, axis=0, mode="fill", fill_value=0)
        sc = jnp.take(scales, page_table, axis=0, mode="fill",
                      fill_value=0.0)
        g = g.astype(jnp.float32) * sc[:, :, None, :, None]
    else:
        g = jnp.take(pool4, page_table, axis=0, mode="fill", fill_value=0)
    s, p, page_size, h, d = g.shape
    return g.reshape(s, p * page_size, h, d)


def read_4d(q, k_pool4, v_pool4, page_table, rel_pos, scale):
    """``paged_read_attention`` as it stood before PR 32: the same walk,
    over ``gather_4d``'s chunks, heads split off the minor axis."""
    codes = k_pool4[0] if isinstance(k_pool4, tuple) else k_pool4
    num_pages, page_size = codes.shape[0], codes.shape[1]
    kv_dtype = jnp.float32 if isinstance(k_pool4, tuple) else codes.dtype
    out_dtype = jnp.result_type(q.dtype, kv_dtype)
    s, t_new, h, d = q.shape
    pages_per_seq = page_table.shape[1]
    cp = _paged.read_chunk_pages(page_size, pages_per_seq)
    chunk = cp * page_size
    pad = -pages_per_seq % cp
    if pad:
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)),
                             constant_values=num_pages)
    trips = _paged.read_trip_count(rel_pos, t_new, page_size, pages_per_seq)
    q_idx = rel_pos[:, None] + jnp.arange(t_new)[None, :]

    def fold(c, carry):
        m, l, acc = carry
        table_c = jax.lax.dynamic_slice_in_dim(page_table, c * cp, cp,
                                               axis=1)
        k_c = gather_4d(k_pool4, table_c)
        v_c = gather_4d(v_pool4, table_c)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_c) * scale
        key_idx = c * chunk + jnp.arange(chunk)
        allow = key_idx[None, None, :] <= q_idx[:, :, None]
        logits = jnp.where(allow[:, None], logits.astype(jnp.float32),
                           -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(jnp.isneginf(logits), 0.0, jnp.exp(logits - m_safe))
        alpha = jnp.exp(m - m_safe)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v_c)
        acc = jnp.swapaxes(alpha, 1, 2) * acc + pv.astype(acc.dtype)
        return m_new, l, acc

    init = (jnp.full((s, h, t_new, 1), -jnp.inf, jnp.float32),
            jnp.zeros((s, h, t_new, 1), jnp.float32),
            jnp.zeros((s, t_new, h, d),
                      jnp.promote_types(out_dtype, jnp.float32)))
    _, l, acc = jax.lax.fori_loop(0, trips, fold, init)
    out = acc / jnp.swapaxes(jnp.maximum(l, 1e-30), 1, 2)
    return out.astype(out_dtype)


def _stored(pool4):
    """A 4-D pool (or ``(codes, scales)``) in the stored shape."""
    if isinstance(pool4, tuple):
        return as_pool(pool4[0]), pool4[1]
    return as_pool(pool4)


def _same(got, want4):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(
        _stored(want4))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", ["live", "padded_lane", "sentinel_page",
                                  "dropped_slot"])
@pytest.mark.parametrize("t_new", [1, 128])
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_write_gather_and_read_equal_the_4d_formulation(int8, t_new, case):
    rng = np.random.default_rng(1000 * t_new + 10 * int8 + len(case))
    num_pages = LANES * PAGES_PER_SEQ + 3
    rel = np.array([0, 150, PAGE * PAGES_PER_SEQ - t_new], np.int32)
    table = np.full((LANES, PAGES_PER_SEQ), num_pages, np.int32)
    perm = rng.permutation(num_pages)
    for i in range(LANES):
        need = -(-int(rel[i] + t_new) // PAGE)
        table[i, :need] = perm[i * PAGES_PER_SEQ:i * PAGES_PER_SEQ + need]
    slots = rel[:, None] + np.arange(t_new, dtype=np.int32)[None, :]
    if case == "padded_lane":               # a lane that only pads
        slots[1, :] = -1
        table[1, :] = num_pages
    elif case == "sentinel_page":           # a hole where a write points
        table[1, rel[1] // PAGE] = num_pages
    elif case == "dropped_slot":            # one token of a live lane
        slots[2, t_new // 2] = -1
    table, slots = jnp.asarray(table), jnp.asarray(slots)

    def pool4():
        x = rng.standard_normal((num_pages, PAGE, H, D)).astype(np.float32)
        return tuple(map(jnp.asarray, quantized(x))) if int8 \
            else jnp.asarray(x)

    k4, v4 = pool4(), pool4()
    new_k, new_v, q = (jnp.asarray(
        3.0 * rng.standard_normal((LANES, t_new, H, D)), jnp.float32)
        for _ in range(3))
    k4w = jax.jit(write_4d)(k4, new_k, table, slots)
    v4w = jax.jit(write_4d)(v4, new_v, table, slots)
    kw = jax.jit(paged_write)(_stored(k4), new_k, table, slots)
    vw = jax.jit(paged_write)(_stored(v4), new_v, table, slots)
    _same(kw, k4w)
    _same(vw, v4w)
    chunk = table[:, :8]
    got = jax.jit(paged_gather, static_argnums=2)(kw, chunk, H)
    want = jnp.transpose(jax.jit(gather_4d)(k4w, chunk), (0, 2, 3, 1))
    assert got.shape == want.shape == (LANES, H, D, 8 * PAGE)
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))
    scale = jnp.float32(1.0 / np.sqrt(D))
    got = paged_read_attention(q, kw, vw, table, jnp.asarray(rel), scale)
    want = jax.jit(read_4d)(q, k4w, v4w, table, jnp.asarray(rel), scale)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))
