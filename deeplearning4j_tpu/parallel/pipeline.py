"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

No reference analog (SURVEY §2.9: PP = NO) — north-star extension. Design
is the standard TPU shift-register schedule (scaling-book style): a stack
of S identical blocks, one per device along the ``pp`` axis, processes M
microbatches in M+S-1 ticks; activations hop stage→stage over
``lax.ppermute`` inside ``shard_map``, and autodiff through the permute
gives exact pipeline-parallel gradients (the transpose of a shift forward
is a shift backward). Stage parameters live only on their stage's device —
memory scales 1/S, unlike a replicated fake pipeline.

Scope: homogeneous stacks (every stage runs the same ``block_fn`` with its
own parameters) — the shape pipeline parallelism is actually used for
(transformer/MLP blocks). Heterogeneous stages belong to tensor/data
parallelism or model surgery, not this schedule.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import rng as _rng

Pytree = Any
BlockFn = Callable[[Pytree, jax.Array], jax.Array]


def make_pipeline_forward(mesh: Mesh, axis: str, block_fn: BlockFn,
                          n_stages: int, n_micro: int,
                          batch_axis: Optional[str] = None):
    """Build ``fn(stacked_params, xm) -> ym``.

    ``stacked_params``: pytree with leading stage axis [S, ...], sharded
    over ``axis``. ``xm``: microbatched input [M, b, ...] (replicated, or
    with the per-microbatch batch dim sharded over ``batch_axis`` for 2-D
    dp x pp meshes — each dp slice then runs its own pipeline).
    Returns [M, b, ...] — the last stage's outputs, with the same batch
    sharding.
    """
    if mesh.shape[axis] != n_stages:
        raise ValueError(
            f"mesh axis {axis!r} has size {mesh.shape[axis]}, "
            f"need n_stages={n_stages}")
    S, M = n_stages, n_micro
    perm = [(i, (i + 1) % S) for i in range(S)]

    def staged(params_blk, xm):
        local = jax.tree_util.tree_map(lambda a: a[0], params_blk)
        s = lax.axis_index(axis)

        def tick(carry, t):
            inflight, outs = carry
            m_in = jnp.clip(t, 0, M - 1)
            first = lax.dynamic_index_in_dim(xm, m_in, 0, keepdims=False)
            x_in = jnp.where(s == 0, first, inflight)
            y = block_fn(local, x_in)
            nxt = lax.ppermute(y, axis, perm)
            # the value reaching stage S-1 at tick t is microbatch t-(S-1);
            # masked select (not lax.cond: branches would differ in
            # mesh-variance type under shard_map's replication tracking)
            m_out = jnp.clip(t - (S - 1), 0, M - 1)
            write = jnp.logical_and(s == S - 1, t >= S - 1)
            updated = lax.dynamic_update_index_in_dim(outs, y, m_out, 0)
            outs = jnp.where(write, updated, outs)
            return (nxt, outs), None

        # carries become device-varying inside the loop (ppermute / masked
        # writes), so their initial values must carry the same
        # mesh-variance type
        inflight0 = lax.pcast(jnp.zeros_like(xm[0]), axis, to="varying")
        outs0 = lax.pcast(jnp.zeros_like(xm), axis, to="varying")
        (_, outs), _ = lax.scan(tick, (inflight0, outs0),
                                jnp.arange(M + S - 1))
        # replicate the last stage's outputs to every device
        return lax.psum(jnp.where(s == S - 1, outs, jnp.zeros_like(outs)),
                        axis)

    x_spec = P(None, batch_axis)

    def fn(stacked_params, xm):
        in_specs = (jax.tree_util.tree_map(lambda _: P(axis),
                                           stacked_params), x_spec)
        return shard_map(staged, mesh=mesh, in_specs=in_specs,
                         out_specs=x_spec)(stacked_params, xm)

    return fn


class PipelineParallelTrainer:
    """Train a stack of S identical blocks pipelined over ``axis``.

    ``layer``: a framework layer config (e.g. ``DenseLayer(n_in=d, n_out=d)``)
    whose ``apply(params, x, ...)`` is pure and shape-preserving; its
    parameters are initialized per stage and stacked [S, ...]. The loss
    head is a plain callable ``loss_fn(y, targets) -> scalar`` evaluated on
    the final stage's (replicated) outputs.
    """

    def __init__(self, layer, n_stages: int, mesh: Mesh, *,
                 axis: str = "pp", n_micro: Optional[int] = None,
                 learning_rate: float = 0.01, loss: str = "mse",
                 seed: int = 0, policy=None):
        from .. import losses as _losses

        self.layer = layer
        self.mesh = mesh
        self.axis = axis
        self.S = int(n_stages)
        self.M = int(n_micro if n_micro is not None else n_stages)
        self.lr = float(learning_rate)

        def block_fn(p, x):
            y, _ = layer.apply(p, x, state=None, train=False, rng=None,
                               policy=policy)
            return y

        # build first: validates n_stages against the mesh axis BEFORE any
        # sharding (a mismatched device_put fails far less readably)
        fwd = make_pipeline_forward(mesh, axis, block_fn, self.S, self.M)

        key = _rng.key(seed)
        per_stage = [layer.init_params(_rng.fold_name(key, f"stage_{i}"),
                                       policy)
                     for i in range(self.S)]
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *per_stage)
        self.params = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(axis, *([None] * (a.ndim - 1))))),
            stacked)
        loss_elem = _losses.get(loss)

        def loss_fn(params, xm, ym):
            out = fwd(params, xm)
            per = loss_elem(ym, out, "identity")
            return jnp.mean(per)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(params, xm, ym):
            loss_val, grads = jax.value_and_grad(loss_fn)(params, xm, ym)
            params = jax.tree_util.tree_map(
                lambda p, g: p - self.lr * g, params, grads)
            return params, loss_val

        self._fwd = jax.jit(fwd)
        self._step = step

    def _microbatch(self, x) -> jax.Array:
        x = jnp.asarray(x)
        b = x.shape[0]
        if b % self.M:
            raise ValueError(f"batch {b} not divisible by n_micro={self.M}")
        return x.reshape((self.M, b // self.M) + x.shape[1:])

    def forward(self, x):
        """Pipelined forward; returns [b, ...] on the host layout."""
        ym = self._fwd(self.params, self._microbatch(x))
        return ym.reshape((-1,) + ym.shape[2:])

    def fit_batch(self, x, y) -> jax.Array:
        xm = self._microbatch(x)
        ym = self._microbatch(y)
        self.params, loss = self._step(self.params, xm, ym)
        return loss


# --------------------------------------------------------------------------
# pipeline parallelism for DSL ComputationGraphs
# --------------------------------------------------------------------------


def _partition_pipeline(conf, pattern: str):
    """Cut a graph's topo order into (prologue, [(block_id, [vertices])],
    epilogue) by the repeated-block naming pattern. Validates the cut is
    actually pipeline-shaped: contiguous blocks, single external input per
    block (the previous block's output), structurally identical stages."""
    topo = conf.topological_order()
    pre: List[str] = []
    blocks: List[Tuple[str, List[str]]] = []
    post: List[str] = []
    for name in topo:
        m = re.match(pattern, name)
        if m:
            if post:
                raise ValueError(
                    f"block vertex {name!r} appears after non-block "
                    f"vertices {post} in topological order — blocks must "
                    "be contiguous to pipeline")
            bid = m.group(1)
            if not blocks or blocks[-1][0] != bid:
                if any(b == bid for b, _ in blocks):
                    raise ValueError(
                        f"block {bid!r} is interleaved with other blocks "
                        "in topological order — cannot pipeline")
                blocks.append((bid, []))
            blocks[-1][1].append(name)
        elif not blocks:
            pre.append(name)
        else:
            post.append(name)
    if not blocks:
        raise ValueError(
            f"no vertices match block pattern {pattern!r}; name repeated "
            "blocks like 'blk0_...' (models/transformer.py style) or pass "
            "block_pattern")
    # structural homogeneity: same suffix sequence AND identical vertex
    # configs in every block — stage s's params run through block 0's
    # vertex objects, so a config drift (e.g. different activation in
    # same-named vertices) would train silently wrong
    def suffix(bid, name):
        return name[len(bid):]
    sig0 = [suffix(blocks[0][0], n) for n in blocks[0][1]]
    for bid, names in blocks[1:]:
        sig = [suffix(bid, n) for n in names]
        if sig != sig0:
            raise ValueError(
                f"block {bid!r} has structure {sig}, expected {sig0} — "
                "stages must be homogeneous to ride the pipeline schedule")
        for n0, n in zip(blocks[0][1], names):
            if conf.vertices[n] != conf.vertices[n0]:
                raise ValueError(
                    f"vertex {n!r} config differs from template {n0!r} — "
                    "stages must be homogeneous to ride the pipeline "
                    "schedule")
    # single external input per block == the previous block's output (or
    # the network input, for graphs whose first block has no prologue)
    prev_out = pre[-1] if pre else conf.network_inputs[0]
    for bid, names in blocks:
        in_block = set(names)
        externals = {src for n in names
                     for src in conf.vertex_inputs[n]
                     if src not in in_block}
        if externals != {prev_out}:
            raise ValueError(
                f"block {bid!r} reads {sorted(externals)} from outside the "
                f"block; a pipeline stage may only read its input "
                f"({prev_out!r})")
        prev_out = names[-1]
    # epilogue may read the last block's output and other epilogue vertices
    allowed = set(post) | {prev_out} | set(conf.network_inputs)
    for n in post:
        for src in conf.vertex_inputs[n]:
            if src not in allowed:
                raise ValueError(
                    f"epilogue vertex {n!r} reads {src!r} from inside the "
                    "pipelined region — cannot pipeline this graph")
    return pre, blocks, post


class GraphPipelineTrainer:
    """GPipe pipeline parallelism for a DSL ``ComputationGraph`` with
    repeated homogeneous blocks — e.g. ``models.transformer.transformer_lm``.

    The graph's topo order is cut by ``block_pattern`` into prologue →
    n_blocks repeated blocks → epilogue. The blocks are distributed over
    the ``axis`` mesh dimension (n_blocks divisible by the axis size; each
    stage runs ``n_blocks/S`` consecutive blocks **with the graph's own
    vertex semantics** — SelfAttentionLayer, LayerNormalization,
    TimeDistributedDense, ElementWiseVertex residuals, ...). Stage params
    live only on their stage's device (1/S memory); microbatches ride the
    shift-register schedule of :func:`make_pipeline_forward`; prologue,
    epilogue and the loss head run replicated and reuse the network's own
    ``_output_score`` math, so the loss/gradients are exactly the
    single-device ones.

    Reference bar: the reference's distributed paths serve arbitrary user
    nets (``ParallelWrapper.java:37-204``); this brings pipeline
    parallelism to the graph DSL instead of bespoke stacks.

    Constraints (validated loudly): stateless, dropout-free vertices inside
    the pipelined region; no l1/l2 regularization (the penalty would need
    the stage-stacked tree remapped); single loss output.
    """

    def __init__(self, net, mesh: Mesh, *, axis: str = "pp",
                 n_micro: Optional[int] = None,
                 batch_axis: Optional[str] = None,
                 block_pattern: str = r"^(blk\d+)_"):
        from ..optimize import updaters as _updaters

        if net.params is None:
            net.init()
        if batch_axis is not None and batch_axis not in mesh.axis_names:
            raise ValueError(f"batch_axis {batch_axis!r} not in mesh "
                             f"{mesh.axis_names}")
        self.net = net
        self.mesh = mesh
        self.axis = axis
        self.batch_axis = batch_axis
        S = int(mesh.shape[axis])
        self.S = S
        self.M = int(n_micro if n_micro is not None else S)
        conf = net.conf
        self.pre, self.blocks, self.post = _partition_pipeline(
            conf, block_pattern)
        if len(self.blocks) % S:
            raise ValueError(
                f"{len(self.blocks)} blocks not divisible by pipeline "
                f"stages {S}")
        self.k = len(self.blocks) // S
        self._validate_pipelineable()
        if len(net._output_layer_names) != 1:
            raise ValueError("pipeline training needs exactly one loss "
                             "output")

        # canonical per-block param structure: [params_of_each_vertex...]
        def block_params(names):
            return [net.params[n] for n in names]

        # stage s = blocks [s*k, (s+1)*k); stack stages on a leading axis
        per_stage = [
            [block_params(self.blocks[s * self.k + j][1])
             for j in range(self.k)]
            for s in range(S)]
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *per_stage)

        def run_vertices(names, params_by_name, acts, mb):
            for n in names:
                xs = [acts[s] for s in conf.vertex_inputs[n]]
                v = conf.vertices[n]
                out, _ = v.apply(params_by_name[n], xs, state={},
                                 train=True, rng=None,
                                 masks=[None] * len(xs),
                                 policy=net.policy, minibatch=mb)
                acts[n] = out
            return acts

        blocks = self.blocks
        k = self.k

        def stage_fn(stage_params, x):
            # stage_params: [k][n_vertices_per_block] param dicts; vertex
            # semantics come from block 0's conf (stages are homogeneous)
            h = x
            for j in range(k):
                names = blocks[j][1]   # structural template
                acts = {conf.vertex_inputs[names[0]][0]: h}
                # external input name differs per block; remap: every
                # external read in the template resolves to h
                ext = {src for n in names for src in conf.vertex_inputs[n]
                       if src not in set(names)}
                for e in ext:
                    acts[e] = h
                pmap = dict(zip(names, stage_params[j]))
                acts = run_vertices(names, pmap, acts, x.shape[0])
                h = acts[names[-1]]
            return h

        fwd = make_pipeline_forward(mesh, axis, stage_fn, S, self.M,
                                    batch_axis=batch_axis)

        pro_names, post_names = self.pre, self.post
        out_name = net._output_layer_names[0]
        consumed = {i for ins in conf.vertex_inputs.values() for i in ins}

        def loss_fn(params, inputs, labels):
            pro, stages, post = params
            B = inputs[0].shape[0]
            acts = dict(zip(conf.network_inputs, inputs))
            acts = run_vertices(pro_names, pro, acts, B)
            h = acts[self.pre[-1]] if self.pre else acts[conf.network_inputs[0]]
            bm = B // self.M
            hm = h.reshape((self.M, bm) + h.shape[1:])
            ym = fwd(stages, hm)
            acts[self.blocks[-1][1][-1]] = ym.reshape((B,) + ym.shape[2:])
            total = 0.0
            for n in post_names:
                if n == out_name:
                    total = total + net._output_score(
                        post, n, acts[conf.vertex_inputs[n][0]],
                        labels[0], None, None, minibatch=B)
                if n != out_name or n in consumed:
                    acts = run_vertices([n], post, acts, B)
            return total.astype(jnp.float32)

        self._updater = _updaters.make_updater(net.training, None)
        pro_params = {n: net.params[n] for n in pro_names}
        post_params = {n: net.params[n] for n in post_names}
        repl = NamedSharding(mesh, P())
        stage_sh = jax.tree_util.tree_map(
            lambda a: NamedSharding(mesh, P(axis, *([None] * (a.ndim - 1)))),
            stacked)
        self.params = (jax.device_put(pro_params, repl),
                       jax.tree_util.tree_map(jax.device_put, stacked,
                                              stage_sh),
                       jax.device_put(post_params, repl))
        self.opt_state = self._updater.init(self.params)
        t = net.training
        norm_kind = t.gradient_normalization
        norm_thr = float(t.gradient_normalization_threshold)
        updater = self._updater

        def step(params, opt_state, inputs, labels, it):
            loss, grads = jax.value_and_grad(loss_fn)(params, inputs, labels)
            grads = _updaters.normalize_gradients(grads, norm_kind, norm_thr)
            deltas, opt_state = updater.update(grads, opt_state, it)
            params = _updaters.apply_updates(params, deltas)
            return params, opt_state, loss

        self._step = jax.jit(step, donate_argnums=(0, 1))
        self._fwd_loss = jax.jit(loss_fn)
        self._batch_sharding = NamedSharding(mesh, P(batch_axis))

    def _validate_pipelineable(self) -> None:
        # the WHOLE graph, not just the pipelined region: the pipeline
        # loss_fn runs every vertex with rng=None (no dropout) and never
        # adds _reg_penalty, so dropout/l1/l2 anywhere would silently
        # diverge from the single-device run — reject loudly instead
        from ..nn.conf.moe import MoELayer

        net, conf = self.net, self.net.conf
        for n in conf.topological_order():
            v = conf.vertices[n]
            if isinstance(getattr(v, "layer", None), MoELayer):
                # run_vertices drops vertex state, so the MoE aux_loss
                # (load balancing) would silently vanish from the pipeline
                # objective and diverge from the single-device loss
                raise ValueError(
                    f"vertex {n!r} is a MoELayer — its aux_loss cannot "
                    "ride the pipeline schedule yet; use "
                    "ExpertParallelGraphTrainer for MoE models")
            if v.init_state(net.policy):
                raise ValueError(
                    f"vertex {n!r} carries state (e.g. BN running stats) — "
                    "pipeline training runs all vertices stateless")
            layer = getattr(v, "layer", None)
            if layer is not None and getattr(layer, "dropout", None):
                raise ValueError(
                    f"vertex {n!r} uses dropout — not supported under "
                    "pipeline training yet")
            if layer is not None and (getattr(layer, "l1", None)
                                      or getattr(layer, "l2", None)):
                raise ValueError(
                    f"vertex {n!r} sets l1/l2 — regularization is not "
                    "supported under pipeline training yet")

    def fit_batch(self, inputs, labels) -> jax.Array:
        """One pipelined update on GLOBAL [b, ...] arrays (b divisible by
        n_micro)."""
        net = self.net
        xs, ys = self._stage_batch(inputs), self._stage_batch(labels)
        from .sequence import _reject_tbptt_chunking
        _reject_tbptt_chunking(net, xs, "GraphPipelineTrainer.fit_batch")
        it = jnp.asarray(net._update_count, jnp.int32)
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, xs, ys, it)
        net._update_count += 1
        net._score = loss
        net._fire_iteration(xs[0].shape[0], loss)
        return loss

    def _stage_batch(self, arrs):
        from .sequence import _as_list
        out = [jax.device_put(jnp.asarray(a), self._batch_sharding)
               for a in _as_list(arrs)]
        if out[0].shape[0] % self.M:
            raise ValueError(f"batch {out[0].shape[0]} not divisible by "
                             f"n_micro={self.M}")
        return out

    def score_for(self, inputs, labels) -> float:
        return float(self._fwd_loss(self.params, self._stage_batch(inputs),
                                    self._stage_batch(labels)))

    def sync_to_net(self) -> None:
        """Write the trained stage params back into ``net.params`` (vertex
        name keyed, fully replicated) so the user's graph can save /
        evaluate / serve as usual."""
        pro, stages, post = self.params
        host = jax.tree_util.tree_map(lambda a: jax.device_get(a), stages)
        net = self.net
        for n, p in pro.items():
            net.params[n] = jax.device_get(p)
        for n, p in post.items():
            net.params[n] = jax.device_get(p)
        for s in range(self.S):
            stage = jax.tree_util.tree_map(lambda a: a[s], host)
            for j in range(self.k):
                _, names = self.blocks[s * self.k + j]
                for name, vparams in zip(names, stage[j]):
                    net.params[name] = vparams
