"""Plain reference of training the OPT decoder: loss, gradients, Adam.

The forward pass is the one in ``opt-1.3b.reference.py`` beside this file
(same block; this configuration only has fewer layers). Added here: the
loss as the program states it (cross-entropy summed over the positions of
each sequence, divided by the number of sequences), its gradients by
``jax.vjp`` layer by layer, and Adam (Kingma & Ba; beta1 0.9, beta2 0.999,
eps 1e-8, bias-corrected, no weight decay). Nothing is imported from the
program under test.

It runs layer by layer so that it fits beside nothing else on one chip: the
forward keeps each layer's input, the backward recomputes one layer at a
time and updates that layer's parameters at once, so no full gradient is
ever held. ``mode`` is the arithmetic of the matrix products (``f32`` the
reference, ``fp8`` the control), as in the forward file.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "opt_reference_forward",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "opt-1.3b.reference.py"))
fwd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fwd)

B1, B2, EPS = 0.9, 0.999, 1e-8


def _head_loss(hp, x, labels, weight, mode, row_block):
    """Summed cross-entropy of one sequence: ``x [t, d]`` residual stream,
    ``labels [t]``, ``weight [t]`` (1 for a counted position). Blocks of
    rows, each rematerialised, so the [rows, V] logits fit."""
    t = x.shape[0]
    n = max(1, t // row_block)

    @jax.checkpoint
    def one(args):
        xb, yb, wb = args
        z = fwd.mm(fwd.layer_norm(xb, hp["lnf_g"], hp["lnf_b"]),
                   hp["head_w"], mode) + hp["head_b"]
        logp = jax.nn.log_softmax(z, axis=-1)
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * wb)

    parts = jax.lax.map(one, (x.reshape(n, t // n, -1),
                              labels.reshape(n, t // n),
                              weight.reshape(n, t // n)))
    return jnp.sum(parts)


@functools.lru_cache(maxsize=None)
def _jit_head_grad(mode: str, row_block: int):
    def f(hp, x, labels, weight):
        loss, (g_hp, dx) = jax.value_and_grad(
            lambda hp, x: _head_loss(hp, x, labels, weight, mode, row_block),
            argnums=(0, 1))(hp, x)
        return loss, g_hp, dx
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _jit_block_grad(n_heads: int, mode: str, q_block: int):
    def f(p, x, dy):
        _, vjp = jax.vjp(functools.partial(
            fwd.block, n_heads=n_heads, mode=mode, q_block=q_block), p, x)
        return vjp(dy)                      # (g_p, dx)
    return jax.jit(f)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(p, m, v, g, lr, step):
    """One Adam update of a dict of leaves; ``step`` counts from 1."""
    out_p, out_m, out_v = {}, {}, {}
    bc1 = 1.0 - jnp.power(B1, step)
    bc2 = 1.0 - jnp.power(B2, step)
    for k in p:
        gk = g[k].astype(jnp.float32)
        out_m[k] = B1 * m[k] + (1.0 - B1) * gk
        out_v[k] = B2 * v[k] + (1.0 - B2) * jnp.square(gk)
        out_p[k] = p[k] - lr * (out_m[k] / bc1) / (
            jnp.sqrt(out_v[k] / bc2) + EPS)
    return out_p, out_m, out_v


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _change_norms(p, start):
    return {k: jnp.sqrt(jnp.sum(jnp.square(p[k] - start[k]))) for k in p}


@functools.partial(jax.jit, static_argnums=(2,))
def _embed_grad(ids, dx, vocab):
    return jnp.zeros((vocab, dx.shape[-1]), jnp.float32).at[ids].add(dx)


@jax.jit
def _diff_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a}


def train_steps(weights, batches, *, cfg: dict, mode: str = "f32",
                token_weight=None, first_grads=None,
                keep_first_grads: bool = False):
    """Run ``len(batches)`` Adam steps from ``weights`` (the flat dict of
    ``lib/weights.py``; CONSUMED: its buffers are donated) on batches of
    ``(ids [b, t], labels [b, t])``. Depth, heads and the learning rate are
    the configuration's (``num_hidden_layers``, ``num_attention_heads``,
    ``learning_rate``), and so are the sizes of the blocks it computes in
    (``reference_q_block`` query rows, ``reference_row_block`` rows of
    logits).

    ``token_weight [t]`` (default all ones) weights each position's loss:
    the planted fault "half of the batch left out, the mean over the rest"
    passes zeros for one half and twos for the other.

    ``first_grads`` (leaf -> host array) are somebody else's gradients of
    the first step, the program's or a control's: the norm of their
    difference from this run's is taken leaf by leaf, as each leaf's
    gradient exists here. ``keep_first_grads`` copies this run's to the
    host for such a comparison (a control put in the program's place).

    Returns a dict of host floats: ``losses`` (each step's), ``grad_norms``
    (each leaf's first gradient), ``change_norms`` (each leaf's change over
    all the steps), ``grad_diff_norms`` (empty without ``first_grads``) and
    ``first_grads`` (empty unless kept).
    """
    n_layers, n_heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    lr = cfg["learning_rate"]
    q_block = cfg.get("reference_q_block", 1024)
    row_block = cfg.get("reference_row_block", 2048)
    p = dict(weights)
    start = {k: jnp.array(v, copy=True) for k, v in p.items()}
    m = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    v_ = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    head_keys = ("lnf_g", "lnf_b", "head_w", "head_b")
    losses, grad_norms, grad_diff_norms, kept = [], {}, {}, {}
    for step, (ids, labels) in enumerate(batches, start=1):
        ids = jnp.asarray(ids, jnp.int32)
        labels = jnp.asarray(labels, jnp.int32)
        b, t = ids.shape
        w = (jnp.ones((t,), jnp.float32) if token_weight is None
             else jnp.asarray(token_weight, jnp.float32))
        qb, rb = min(q_block, t), min(row_block, t)
        blk = fwd._jit_block(n_heads, mode, qb)
        blk_grad = _jit_block_grad(n_heads, mode, qb)
        # forward, keeping every layer's input (b is small: a Python loop)
        xs = [[jnp.take(p["embed"], ids[r], axis=0).astype(jnp.float32)]
              for r in range(b)]
        for i in range(n_layers):
            lp = fwd.layer_params(p, i)
            for r in range(b):
                xs[r].append(blk(lp, xs[r][i]))
        # head: loss and the gradient flowing back into the stream
        hp = {k: p[k] for k in head_keys}
        loss, g_head, dxs = 0.0, None, []
        for r in range(b):
            l_r, g_r, dx = _jit_head_grad(mode, rb)(hp, xs[r][n_layers],
                                                    labels[r], w / b)
            loss = loss + l_r
            g_head = g_r if g_head is None else jax.tree_util.tree_map(
                jnp.add, g_head, g_r)
            dxs.append(dx)
        losses.append(float(loss))

        def update(keys, grads):
            if step == 1:
                grad_norms.update({k: float(n) for k, n in
                                   _norms(grads).items()})
                if first_grads is not None:
                    theirs = {k: jnp.asarray(first_grads[k], jnp.float32)
                              for k in keys}
                    grad_diff_norms.update(
                        {k: float(n) for k, n in _diff_norms(
                            {k: grads[k].astype(jnp.float32) for k in keys},
                            theirs).items()})
                if keep_first_grads:
                    kept.update({k: np.asarray(grads[k], np.float32)
                                 for k in keys})
            sub = lambda d: {k: d.pop(k) for k in keys}      # noqa: E731
            new_p, new_m, new_v = _adam(sub(p), sub(m), sub(v_), grads,
                                        jnp.float32(lr), jnp.float32(step))
            p.update(new_p), m.update(new_m), v_.update(new_v)

        update(head_keys, g_head)
        for i in reversed(range(n_layers)):
            lp = fwd.layer_params(p, i)
            g_layer = None
            for r in range(b):
                g_r, dxs[r] = blk_grad(lp, xs[r][i], dxs[r])
                xs[r][i + 1] = None
                g_layer = g_r if g_layer is None else \
                    jax.tree_util.tree_map(jnp.add, g_layer, g_r)
            update([f"l{i}.{k}" for k in fwd.LAYER_KEYS],
                   {f"l{i}.{k}": g for k, g in g_layer.items()})
        g_embed = None
        for r in range(b):
            g_r = _embed_grad(ids[r], dxs[r], p["embed"].shape[0])
            g_embed = g_r if g_embed is None else g_embed + g_r
        update(["embed"], {"embed": g_embed})
    change = _change_norms(p, start)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(n) for k, n in change.items()},
            "grad_diff_norms": grad_diff_norms, "first_grads": kept}
