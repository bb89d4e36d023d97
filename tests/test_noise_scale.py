"""The DP noise's scale, sigma x sensitivity, is applied in f32 and the
noise rounded once to the gradient's dtype: under a multi-group policy the
noise is the definition's (sigma * sqrt(sum R^2) * N(0, 1), the reference
of bench/reference/noise.py) to the gradient dtype's precision, and at a
scale of 1 the output is what it was when the scale was applied in the
gradient's dtype."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.noise import (GaussianMechanism, TreeAggregationMechanism,
                              _path_rng, add_noise, counter_normal)

BF16 = jnp.bfloat16
# sqrt(0.5^2 + 0.25^2 + 1^2): deepseek-moe-16b's experts/router/dense groups
SENS = math.sqrt(0.5 ** 2 + 0.25 ** 2 + 1.0 ** 2)
PATH = "blocks/mlp/experts/up/w"
SHAPE = (64, 1024)


def _reference(rng, path, shape, scale):
    """The definition, in f32: the counter draw of the leaf, times scale."""
    return scale * counter_normal(_path_rng(rng, path), shape)


@pytest.mark.parametrize("add", ["add_leaf", "add_noise"])
def test_noise_is_the_reference_to_bf16_precision(add):
    rng = jax.random.PRNGKey(7)
    zero = jnp.zeros(SHAPE, BF16)
    if add == "add_leaf":
        got = GaussianMechanism().add_leaf(PATH, zero, rng, 1.0, SENS, 1.0)
    else:
        got = add_noise({PATH: zero}, rng, 1.0, SENS, 1.0)[PATH]
    want = np.asarray(_reference(rng, PATH, SHAPE, SENS))
    ratio = np.asarray(got, np.float32)[want != 0] / want[want != 0]
    # one rounding to bf16: half an ulp, at most 2^-8 of the value
    assert np.abs(ratio - 1).max() <= 2.0 ** -8
    assert abs(ratio.mean() - 1) < 1e-4
    # the scale rounded to bf16 first (1.1484375) is 0.244% too much noise
    assert abs(float(jnp.asarray(SENS, BF16)) / SENS - 1) > 2e-3


@pytest.mark.parametrize("dtype", [BF16, jnp.float32])
def test_scale_one_is_bitwise_unchanged(dtype):
    rng = jax.random.PRNGKey(3)
    g = jax.random.normal(jax.random.PRNGKey(5), SHAPE).astype(dtype)
    got = GaussianMechanism().add_leaf(PATH, g, rng, 1.0, 1.0, 4.0)
    xi = counter_normal(_path_rng(rng, PATH), SHAPE)
    before = (g + (1.0 * 1.0) * xi.astype(g.dtype)) / 4.0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(before))


def test_tree_noise_scale_applied_in_f32():
    """The tree mechanism's node noise is scaled the same way."""
    mech = TreeAggregationMechanism(seed=2, depth=4)
    zero = jnp.zeros((8, 128), BF16)
    got = mech.add_leaf(PATH, zero, None, 1.0, SENS, 1.0, step=0)
    unit = mech.add_leaf(PATH, jnp.zeros((8, 128), jnp.float32), None, 1.0,
                         1.0, 1.0, step=0)
    want = np.asarray(unit) * SENS
    ratio = np.asarray(got, np.float32)[want != 0] / want[want != 0]
    assert np.abs(ratio - 1).max() <= 2.0 ** -8
