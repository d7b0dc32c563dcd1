"""Unit tests for the functional op layer (activations, losses, inits).

Mirrors the reference's ND4J-op-level unit coverage (SURVEY.md §4:
construct small inputs, assert hand-computed values).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.activations import Activation, activate, activation_gradient
from deeplearning4j_tpu.ops.losses import (LossFunction, _masked_mean,
                                           compute_loss, target_value)
from deeplearning4j_tpu.nn.weights import WeightInit, init_weights


class TestActivations:
    def test_relu(self):
        x = jnp.array([-2.0, -0.5, 0.0, 1.5])
        np.testing.assert_allclose(activate("relu", x), [0, 0, 0, 1.5])

    def test_sigmoid_values(self):
        x = jnp.array([0.0])
        np.testing.assert_allclose(activate("sigmoid", x), [0.5])

    def test_softmax_rows_sum_to_one(self):
        x = jnp.arange(12.0).reshape(3, 4)
        s = activate("softmax", x)
        np.testing.assert_allclose(jnp.sum(s, axis=-1), np.ones(3), rtol=1e-6)

    def test_hardtanh(self):
        x = jnp.array([-5.0, -0.3, 0.3, 5.0])
        np.testing.assert_allclose(activate("hardtanh", x), [-1, -0.3, 0.3, 1])

    def test_cube(self):
        np.testing.assert_allclose(activate("cube", jnp.array([2.0])), [8.0])

    @pytest.mark.parametrize("name", [a for a in Activation if a is not Activation.SOFTMAX])
    def test_gradient_matches_jax(self, name):
        x = jnp.linspace(-2.0, 2.0, 7)
        g = activation_gradient(name, x)
        g_ref = jax.vmap(jax.grad(lambda v: activate(name, v)))(x)
        np.testing.assert_allclose(g, g_ref, rtol=1e-6, atol=1e-6)

    def test_all_finite_on_extremes(self):
        x = jnp.array([-50.0, 50.0])
        for a in Activation:
            y = activate(a, x)
            assert bool(jnp.all(jnp.isfinite(y))), a


class TestLosses:
    def test_mse_hand_computed(self):
        # DL4J convention: sum of squared error over features, mean over batch
        labels = jnp.array([[1.0, 0.0], [0.0, 1.0]])
        preds = jnp.array([[0.5, 0.5], [0.0, 1.0]])
        val = compute_loss("mse", labels, preds)
        np.testing.assert_allclose(val, (0.25 + 0.25) / 2.0, rtol=1e-6)

    def test_mcxent_one_hot(self):
        labels = jnp.array([[1.0, 0.0]])
        preds = jnp.array([[0.25, 0.75]])
        np.testing.assert_allclose(compute_loss("mcxent", labels, preds), -np.log(0.25), rtol=1e-5)

    def test_mcxent_from_logits_matches_softmax_path(self):
        key = jax.random.PRNGKey(0)
        logits = jax.random.normal(key, (4, 5))
        labels = jax.nn.one_hot(jnp.array([0, 2, 4, 1]), 5)
        a = compute_loss("mcxent", labels, jax.nn.softmax(logits), from_logits=False)
        b = compute_loss("mcxent", labels, logits, from_logits=True)
        np.testing.assert_allclose(a, b, rtol=1e-5)

    def test_xent_from_logits_matches_sigmoid_path(self):
        logits = jnp.array([[0.3, -1.2, 2.0]])
        labels = jnp.array([[1.0, 0.0, 1.0]])
        a = compute_loss("xent", labels, jax.nn.sigmoid(logits), from_logits=False)
        b = compute_loss("xent", labels, logits, from_logits=True)
        np.testing.assert_allclose(a, b, rtol=1e-5)

    def test_mask_excludes_examples(self):
        labels = jnp.array([[1.0], [1.0]])
        preds = jnp.array([[0.0], [1.0]])
        mask = jnp.array([1.0, 0.0])
        # only first example counts: (1-0)^2 = 1
        np.testing.assert_allclose(compute_loss("mse", labels, preds, mask=mask), 1.0)

    @pytest.mark.parametrize("name", list(LossFunction))
    def test_all_losses_finite_and_scalar(self, name):
        key = jax.random.PRNGKey(3)
        labels = jax.nn.softmax(jax.random.normal(key, (6, 4)))
        preds = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(4), (6, 4)))
        v = compute_loss(name, labels, preds)
        assert v.shape == ()
        assert bool(jnp.isfinite(v))


class TestWeightInit:
    def test_zero_ones(self):
        k = jax.random.PRNGKey(0)
        assert float(jnp.sum(init_weights(k, (3, 3), "zero", 3, 3))) == 0.0
        assert float(jnp.sum(init_weights(k, (3, 3), "ones", 3, 3))) == 9.0

    def test_xavier_std(self):
        k = jax.random.PRNGKey(1)
        w = init_weights(k, (500, 500), WeightInit.XAVIER, 500, 500)
        expected = np.sqrt(2.0 / 1000.0)
        assert abs(float(jnp.std(w)) - expected) < 0.1 * expected

    def test_uniform_bounds(self):
        k = jax.random.PRNGKey(2)
        w = init_weights(k, (100, 100), WeightInit.UNIFORM, 100, 100)
        a = 1.0 / np.sqrt(100)
        assert float(jnp.max(jnp.abs(w))) <= a

    def test_deterministic_given_key(self):
        k = jax.random.PRNGKey(7)
        w1 = init_weights(k, (4, 4), "xavier", 4, 4)
        w2 = init_weights(k, (4, 4), "xavier", 4, 4)
        np.testing.assert_array_equal(w1, w2)


def test_sparse_mcxent_matches_onehot(rng):
    """Integer-id labels == one-hot labels for mcxent/nll, logits and
    probability paths, 2-D and 3-D, masked and not."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.losses import compute_loss

    b, t, c = 4, 5, 7
    logits = jnp.asarray(rng.standard_normal((b, t, c)), jnp.float32)
    ids = rng.integers(0, c, (b, t))
    onehot = jnp.asarray(np.eye(c, dtype=np.float32)[ids])
    sparse = jnp.asarray(ids, jnp.float32)
    mask = jnp.asarray((rng.random((b, t)) > 0.4), jnp.float32)
    for from_logits in (True, False):
        preds = logits if from_logits else jax.nn.softmax(logits, axis=-1)
        for m in (None, mask):
            a = compute_loss("mcxent", onehot, preds, mask=m,
                             from_logits=from_logits)
            s = compute_loss("mcxent", sparse, preds, mask=m,
                             from_logits=from_logits)
            np.testing.assert_allclose(np.asarray(s), np.asarray(a),
                                       rtol=1e-6, atol=1e-7)
    # 2-D case
    a2 = compute_loss("negativeloglikelihood", onehot[:, 0], logits[:, 0],
                      from_logits=True)
    s2 = compute_loss("negativeloglikelihood", sparse[:, 0], logits[:, 0],
                      from_logits=True)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(a2), rtol=1e-6)
    # sparse labels reject non-xent losses loudly
    import pytest
    with pytest.raises(ValueError, match="sparse"):
        compute_loss("mse", sparse, logits)


def test_sparse_mcxent_ignore_index(rng):
    """Negative ids contribute zero loss and are excluded from the mean
    (the ignore-index convention)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.losses import compute_loss

    logits = jnp.asarray(rng.standard_normal((6, 5)), jnp.float32)
    ids = rng.integers(0, 5, 6)
    sparse = jnp.asarray(ids, jnp.float32)
    ignored = sparse.at[2].set(-1.0).at[4].set(-1.0)
    keep = jnp.asarray([1, 1, 0, 1, 0, 1], jnp.float32)
    want = compute_loss("mcxent", sparse, logits, mask=keep, from_logits=True)
    got = compute_loss("mcxent", ignored, logits, from_logits=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def _gathered_loss(ids, preds, mask, from_logits):
    """The sparse branch as it stood: the target picked by a gather from the
    predictions flattened to 2-D."""
    ids = ids.astype(jnp.int32)
    pred2 = preds.reshape(-1, preds.shape[-1])
    tgt = jnp.take_along_axis(
        pred2, jnp.clip(ids, 0, None).reshape(-1, 1), axis=1)[:, 0]
    if from_logits:
        per_ex = jax.scipy.special.logsumexp(pred2, axis=-1) - tgt
    else:
        per_ex = -jnp.log(jnp.clip(tgt, 1e-7, 1.0))
    keep = (ids >= 0).astype(jnp.float32)
    if mask is not None:
        keep = mask * keep
    return _masked_mean(per_ex.reshape(ids.shape), keep)


@pytest.mark.parametrize("ids_are", ["all kept", "masked", "some ignored",
                                     "all ignored"])
@pytest.mark.parametrize("from_logits", [True, False],
                         ids=["logits", "probabilities"])
@pytest.mark.parametrize("lead", [(6,), (3, 17)], ids=["2-D", "3-D"])
def test_sparse_target_is_the_gathers_value_and_gradient(
        rng, lead, from_logits, ids_are):
    """The masked sum over the class axis against ``take_along_axis``: the
    loss bitwise (a sum of one value and zeros), the gradient with respect to
    the predictions to 1e-6."""
    c = 257
    logits = jnp.asarray(rng.standard_normal(lead + (c,)), jnp.float32)
    preds = logits if from_logits else jax.nn.softmax(logits, axis=-1)
    ids = rng.integers(0, c, lead).astype(np.float32)
    mask = None
    if ids_are == "masked":
        mask = jnp.asarray(rng.random(lead) > 0.4, jnp.float32)
    elif ids_are == "some ignored":
        ids[rng.random(lead) > 0.6] = -1.0
        ids.flat[0] = -100.0
    elif ids_are == "all ignored":
        ids[...] = -1.0
    ids = jnp.asarray(ids)
    got, g_got = jax.value_and_grad(lambda z: compute_loss(
        "mcxent", ids, z, mask=mask, from_logits=from_logits))(preds)
    want, g_want = jax.value_and_grad(
        lambda z: _gathered_loss(ids, z, mask, from_logits))(preds)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    np.testing.assert_allclose(g_got, g_want, rtol=1e-6, atol=1e-6)
    if ids_are == "all ignored":
        assert float(got) == 0.0 and not np.any(g_got)


def test_target_value_reads_the_last_axis_of_any_rank(rng):
    z = jnp.asarray(rng.standard_normal((2, 3, 4, 9)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 9, (2, 3, 4)), jnp.int32)
    want = jnp.take_along_axis(z, ids[..., None], axis=-1)[..., 0]
    np.testing.assert_array_equal(target_value(z, ids), want)
    # a negative id reads class 0 (the loss masks it out)
    np.testing.assert_array_equal(target_value(z, -ids - 1), z[..., 0])


class TestMaxpoolMaskVJP:
    """The opt-in equality-mask maxpool backward (ops/pooling.py)."""

    def test_matches_xla_backward_on_distinct_values(self, rng):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from deeplearning4j_tpu.ops.pooling import maxpool2d

        x = jnp.asarray(rng.permutation(8 * 9 * 9 * 3).reshape(8, 9, 9, 3),
                        jnp.float32)

        def ref(x):
            return jnp.sum(lax.reduce_window(
                x * x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                ((0, 0), (1, 1), (1, 1), (0, 0))))

        def got(x):
            return jnp.sum(maxpool2d(x * x, (3, 3), (2, 2), (1, 1)))

        np.testing.assert_allclose(np.asarray(jax.grad(got)(x)),
                                   np.asarray(jax.grad(ref)(x)), rtol=1e-6)

    def test_tie_mass_preserved(self, rng):
        """With exact ties, each window's gradient splits evenly across
        maximal cells — total mass per window preserved (ADVICE r3)."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops.pooling import maxpool2d

        x = jnp.ones((1, 4, 4, 1), jnp.float32)  # every cell ties
        g = jax.grad(lambda x: jnp.sum(maxpool2d(x, (2, 2), (2, 2), (0, 0))))(x)
        # 4 windows, each distributing 1.0 over 4 tied cells
        np.testing.assert_allclose(np.asarray(g), 0.25)
        assert float(jnp.sum(g)) == 4.0
