"""``losses.sparse_mcxent``: the value, its own backward, and what the
lowered program must not hold (CPU).

The oracle is ``mcxent`` on one-hot labels in float32 for values and
gradients, and the formula ``sparse_mcxent`` had before its own backward
(autodiff through ``log_softmax`` + ``take_along_axis``), kept here and
nowhere in the package, for the numerics ordering and the scatter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import losses as L

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
SHAPES = {"b": (6,), "bt": (3, 5)}
WIDTHS = (7, 1003)          # 1003: like 50272, no multiple of a lane tile


def autodiff_sparse_mcxent(labels, pre, activation="softmax"):
    """The parent's formula: its gather transposes to a scatter-add into a
    dense [.., V] cotangent, and its softmax comes back as ``exp`` of a
    ``log_softmax`` rounded to ``pre``'s dtype."""
    logp = jax.nn.log_softmax(pre, axis=-1)
    ids = labels.astype(jnp.int32)
    return -jnp.take_along_axis(logp, ids[..., None], axis=-1,
                                mode="fill", fill_value=jnp.nan)[..., 0]


def draw(seed, lead, v, dtype, scale=3.0):
    rng = np.random.default_rng(seed)
    pre = jnp.asarray(rng.normal(size=lead + (v,)) * scale, jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, lead), jnp.int32)
    return pre.astype(dtype), ids


def dense_truth(pre, ids, weights=None):
    """Per-row loss and d(sum of weighted rows)/d pre from ``mcxent`` on
    one-hot labels, all in float32 on the values ``pre`` holds."""
    x = pre.astype(jnp.float32)
    onehot = jax.nn.one_hot(ids, x.shape[-1], dtype=jnp.float32)
    w = jnp.ones(ids.shape, jnp.float32) if weights is None else weights

    def total(x):
        rows = jnp.sum(L.mcxent(onehot, x, "softmax"), axis=-1)
        return jnp.sum(rows * w), rows
    (_, rows), grad = jax.value_and_grad(total, has_aux=True)(x)
    return rows, grad


def tolerances(dtype):
    # bf16: the returned loss and gradient are rounded once to 8 bits
    return ({"rtol": 2e-5, "atol": 2e-6} if dtype == jnp.float32
            else {"rtol": 1e-2, "atol": 1e-3})


@pytest.mark.parametrize("v", WIDTHS)
@pytest.mark.parametrize("lead", SHAPES.values(), ids=SHAPES.keys())
@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES.keys())
class TestAgainstDenseOneHot:
    def test_value_and_gradient(self, dtype, lead, v):
        pre, ids = draw(1, lead, v, dtype)
        rows, grad = dense_truth(pre, ids)
        fn = L.get("sparse_mcxent")
        got = fn(ids, pre, "softmax")
        assert got.shape == lead and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(rows), **tolerances(dtype))
        g = jax.grad(lambda x: jnp.sum(
            fn(ids, x, "softmax").astype(jnp.float32)))(pre)
        assert g.shape == pre.shape and g.dtype == dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(grad), **tolerances(dtype))

    def test_row_mask_through_score_array(self, dtype, lead, v):
        pre, ids = draw(2, lead, v, dtype)
        mask = jnp.asarray(
            np.random.default_rng(3).integers(0, 2, lead), jnp.float32)
        mask = mask.at[(0,) * len(lead)].set(0.0)   # at least one row out
        rows, grad = dense_truth(pre, ids, mask)

        def total(x):
            per = L.score_array("sparse_mcxent", ids, x, "softmax",
                                mask.astype(dtype))
            return jnp.sum(per.astype(jnp.float32)), per
        (_, per), g = jax.value_and_grad(total, has_aux=True)(pre)
        want = rows * mask
        want = jnp.sum(want, axis=tuple(range(1, want.ndim))) \
            if want.ndim > 1 else want
        tol = tolerances(dtype)
        if dtype == jnp.bfloat16:       # a bf16 sum over the row's steps
            tol = {"rtol": 3e-2, "atol": 1e-2}
        np.testing.assert_allclose(np.asarray(per, np.float32),
                                   np.asarray(want), **tol)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(grad), **tolerances(dtype))
        masked_rows = np.asarray(g, np.float32)[np.asarray(mask) == 0]
        assert not masked_rows.any()

    def test_id_out_of_range(self, dtype, lead, v):
        pre, ids = draw(4, lead, v, dtype)
        first = (0,) * len(lead)
        fn = L.get("sparse_mcxent")
        want = np.array(dense_truth(pre, ids)[1])
        want[first] = 0.0
        for bad_id in (v, -1):
            bad = ids.at[first].set(bad_id)
            per = np.array(fn(bad, pre, "softmax"), np.float32)
            assert np.isnan(per[first])
            per[first] = 0.0
            assert np.isfinite(per).all()
            # the step's loss is poisoned; the other rows' gradients (as
            # a skip budget would find them) are sound, the bad row has none
            g = jax.grad(lambda x: jnp.sum(
                fn(bad, x, "softmax").astype(jnp.float32)))(pre)
            g = np.asarray(g, np.float32)
            assert np.isfinite(g).all()
            assert not g[first].any()
            np.testing.assert_allclose(g, want, **tolerances(dtype))

    def test_jit_and_vmap(self, dtype, lead, v):
        pre, ids = draw(5, (4,) + lead, v, dtype)
        fn = L.get("sparse_mcxent")

        def total(x, i):
            return jnp.sum(fn(i, x, "softmax").astype(jnp.float32))
        whole = jax.jit(jax.grad(total))(pre, ids)
        mapped = jax.jit(jax.vmap(jax.grad(total)))(pre, ids)
        np.testing.assert_allclose(np.asarray(mapped, np.float32),
                                   np.asarray(whole, np.float32),
                                   rtol=1e-6, atol=1e-7)
        _, grad = dense_truth(pre, ids)
        np.testing.assert_allclose(np.asarray(whole, np.float32),
                                   np.asarray(grad), **tolerances(dtype))

    def test_twice_under_checkpoint(self, dtype, lead, v):
        pre, ids = draw(6, lead, v, dtype)
        other = jnp.roll(ids, 1, axis=0)
        fn = L.get("sparse_mcxent")

        @jax.checkpoint
        def both(x):
            a = fn(ids, x, "softmax").astype(jnp.float32)
            b = fn(other, x * 0.5, "softmax").astype(jnp.float32)
            return jnp.sum(a) + jnp.sum(b)
        g = jax.jit(jax.grad(both))(pre)
        _, ga = dense_truth(pre, ids)
        _, gb = dense_truth(pre * 0.5, other)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(ga + 0.5 * gb),
                                   **tolerances(dtype))


@pytest.mark.parametrize("scale", (1.0, 3.0))
@pytest.mark.parametrize("v", (1003, 8192))
def test_bf16_gradient_lies_closer_to_float32_than_autodiffs(v, scale):
    """The numerics claim: ``exp`` of a float32 difference of the bf16
    logits against autodiff's softmax, an ``exp`` of a difference rounded
    to bf16. Element by element, since the norm of a row's gradient is
    its label's entry and hides the other V - 1."""
    pre, ids = draw(7, (16,), v, jnp.bfloat16, scale=scale)
    _, truth = dense_truth(pre, ids)

    def rel_err(fn):
        g = jax.grad(lambda x: jnp.sum(
            fn(ids, x, "softmax").astype(jnp.float32)))(pre)
        return np.abs(np.asarray(g.astype(jnp.float32) - truth)
                      / np.asarray(truth))
    own, autodiff = rel_err(L.get("sparse_mcxent")), rel_err(autodiff_sparse_mcxent)
    assert own.max() <= 2.0 ** -8       # the result's one rounding to bf16
    assert autodiff.max() > 5 * own.max(), (own.max(), autodiff.max())
    assert autodiff.mean() > 3 * own.mean(), (own.mean(), autodiff.mean())


def test_float64_logits_keep_their_precision():
    pre, ids = draw(8, (5,), 11, jnp.float64)
    got = L.get("sparse_mcxent")(ids, pre, "softmax")
    assert got.dtype == jnp.float64
    want = autodiff_sparse_mcxent(ids, pre)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-13, atol=1e-13)
    g = jax.grad(lambda x: jnp.sum(L.get("sparse_mcxent")(ids, x, "softmax")))
    g0 = jax.grad(lambda x: jnp.sum(autodiff_sparse_mcxent(ids, x)))
    np.testing.assert_allclose(np.asarray(g(pre)), np.asarray(g0(pre)),
                               rtol=1e-12, atol=1e-13)


def test_ids_of_any_integer_or_float_type_and_the_alias():
    pre, ids = draw(9, (4,), 7, jnp.float32)
    want = np.asarray(L.get("sparse_mcxent")(ids, pre, "softmax"))
    for cast in (np.int64, np.uint8, np.float32):
        got = L.get("sparse_categorical_crossentropy")(
            np.asarray(ids).astype(cast), pre, "softmax")
        np.testing.assert_array_equal(np.asarray(got), want)
    with pytest.raises(ValueError, match="softmax"):
        L.get("sparse_mcxent")(ids, pre, "identity")


# --------------------------------------------------------------------------
# structure of the traced program: the mechanism's guard, in place of a
# counter (the body has no rate of engagement: it is the only one)
# --------------------------------------------------------------------------

V_LM, T_LM = 97, 12


def scoped_eqns(jaxpr, prefix=""):
    """Every equation of a jaxpr and of the jaxprs in its parameters, as
    (name stack from the top, equation, the jaxpr that holds it)."""
    for eqn in jaxpr.eqns:
        stack = f"{prefix}/{eqn.source_info.name_stack}"
        yield stack, eqn, jaxpr
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list)) else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from scoped_eqns(sub, stack)


def loss_scope_findings(closed_jaxpr, v):
    """What the ``loss`` scope of a traced gradient holds: its primitives,
    and the [.., v]-shaped values its forward part computes and its
    backward part reads (what the loss stores over the class axis for its
    backward, beside the logits it was handed)."""
    forward, backward, prims = {}, {}, set()
    for stack, eqn, holder in scoped_eqns(closed_jaxpr.jaxpr):
        if "jvp(loss)" not in stack:
            continue
        prims.add(eqn.primitive.name)
        if "transpose(jvp(loss))" in stack:
            backward.setdefault(id(holder), set()).update(
                x for x in eqn.invars if hasattr(x, "count"))
        else:
            forward.setdefault(id(holder), set()).update(
                x for x in eqn.outvars
                if x.aval.ndim >= 2 and x.aval.shape[-1] == v)
    stored = [str(x.aval) for key, made in forward.items()
              for x in made if x in backward.get(key, ())]
    return prims, stored


def lm_train_step_jaxpr(monkeypatch=None, formula=None):
    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
    if formula is not None:
        monkeypatch.setitem(L._REGISTRY, "sparse_mcxent", formula)
    net = ComputationGraph(transformer_lm(
        V_LM, n_layers=1, d_model=16, n_heads=2, d_ff=32, seed=3,
        input_ids=True, dtype="mixed_bf16")).init()
    seen = {}

    class Traced(Exception):
        pass

    def capture(*args):
        seen["jaxpr"] = jax.make_jaxpr(net._make_train_step())(*args)
        raise Traced
    net._train_step = lambda: capture
    ids = np.arange(2 * T_LM, dtype=np.int32).reshape(2, T_LM) % V_LM
    with pytest.raises(Traced):
        net.fit([ids], [(ids + 1) % V_LM])
    return seen["jaxpr"]


def test_train_step_of_an_lm_on_ids_scatters_and_stores_nothing_under_loss(
        monkeypatch):
    prims, stored = loss_scope_findings(lm_train_step_jaxpr(), V_LM)
    assert {"exp", "reduce_sum", "reduce_max"} <= prims, \
        "the step lost its `loss` scope"
    assert not {p for p in prims if "scatter" in p or "gather" in p}
    assert stored == []
    # the oracle: the same step on the parent's formula scatters the row
    # cotangents into a dense [b, t, V] array and keeps its softmax
    prims, stored = loss_scope_findings(
        lm_train_step_jaxpr(monkeypatch, autodiff_sparse_mcxent), V_LM)
    assert "scatter-add" in prims and "gather" in prims
    assert stored == [f"bfloat16[2,{T_LM},{V_LM}]"]


def test_loss_alone_lowers_without_scatter_and_the_parents_with_one():
    pre, ids = draw(10, (2, T_LM), V_LM, jnp.bfloat16)

    def lowered(fn):
        def total(x):
            with jax.named_scope("loss"):
                return jnp.sum(fn(ids, x, "softmax").astype(jnp.float32))
        return jax.jit(jax.value_and_grad(total)).lower(pre).as_text()
    own, parent = lowered(L.get("sparse_mcxent")), lowered(autodiff_sparse_mcxent)
    assert "scatter" not in own and "gather" not in own
    assert "stablehlo.scatter" in parent and "stablehlo.gather" in parent
    wide = f"tensor<2x{T_LM}x{V_LM}xbf16>"
    # the parent: zeros over the class axis for the scatter to fill
    before_scatter = parent.split('"stablehlo.scatter"')[0].splitlines()
    assert "stablehlo.broadcast_in_dim" in before_scatter[-2] \
        and before_scatter[-2].rstrip().endswith(wide)
    # own: the one [.., V] value in bf16 it makes is the gradient it returns
    made = [l for l in own.splitlines()
            if l.rstrip().endswith(wide) and " = stablehlo." in l]
    assert len(made) == 1 and "stablehlo.convert" in made[0], made
