"""``ops/headnorm.py``: a norm a head and a rotation by position in one pass
over ``[B, T, H * 128]`` rows (the kernels interpreted here, the code the chip
runs), against the same equations on ``[B, T, H, 128]`` arrays by hand —
``RMSNorm``'s and ``rotate_by_position(..., "halves")``'s — value, the rows'
gradient and the weight's, with either part left out, at one head and at
several, over one grid step and over several."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_autoaugment_tpu.models.token_blocks import rotate_by_position
from fast_autoaugment_tpu.ops.headnorm import head_norm_rotate

THETA = 10000.0


def _angle(length):
    inverse = THETA ** (-jnp.arange(0, 128, 2, dtype=jnp.float32) / 128)
    return jnp.arange(length, dtype=jnp.float32)[:, None] * inverse


def _by_hand(x, weight, heads, norm, rotate):
    batch, length, _ = x.shape
    cut = x.reshape(batch, length, heads, 128)
    if norm:
        cut = cut * jax.lax.rsqrt(jnp.mean(cut * cut, -1, keepdims=True) + 1e-5) * weight
    if rotate:
        cut = rotate_by_position(cut, THETA, "halves")
    return cut.reshape(x.shape)


@pytest.mark.parametrize("norm, rotate", [(True, True), (True, False), (False, True)],
                         ids=["norm_and_rotation", "norm_alone", "rotation_alone"])
@pytest.mark.parametrize("batch, length, heads", [(1, 256, 1), (2, 512, 4), (1, 384, 2)],
                         ids=["one_step", "four_steps", "a_length_no_block_divides"])
def test_one_pass_over_the_rows_is_the_norm_and_the_rotation_by_hand(
        batch, length, heads, norm, rotate):
    keys = jax.random.split(jax.random.PRNGKey(length + heads), 3)
    x = 3.0 * jax.random.normal(keys[0], (batch, length, heads * 128))
    weight = 1.0 + 0.3 * jax.random.normal(keys[1], (128,))
    cotangent = jax.random.normal(keys[2], x.shape)

    def ours(x, weight):
        return jnp.sum(cotangent * head_norm_rotate(
            x, heads, weight=weight if norm else None, eps=1e-5,
            angle=_angle(length) if rotate else None))

    def theirs(x, weight):
        return jnp.sum(cotangent * _by_hand(x, weight, heads, norm, rotate))

    mine, mine_grads = jax.value_and_grad(ours, (0, 1))(x, weight)
    hand, hand_grads = jax.value_and_grad(theirs, (0, 1))(x, weight)
    assert float(mine) == pytest.approx(float(hand), rel=1e-5, abs=1e-3)
    out = head_norm_rotate(x, heads, weight=weight if norm else None, eps=1e-5,
                           angle=_angle(length) if rotate else None)
    assert out.shape == x.shape and out.dtype == jnp.float32
    assert np.abs(np.asarray(out - _by_hand(x, weight, heads, norm, rotate))).max() <= 1e-5
    for got, want in zip(mine_grads, hand_grads):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 2e-6 * max(
            np.abs(np.asarray(want)).max(), 1.0)


def test_rows_that_are_no_whole_heads_are_refused():
    with pytest.raises(ValueError, match="heads of 128 lanes"):
        head_norm_rotate(jnp.zeros((1, 256, 192)), 3)
