"""Heads of 64 in ``ops/attention.py``: the fused kernels take two adjacent
heads to a 128-lane block of ``[B, T, H * 64]`` as it lies (interpreted
here, the code the chip runs), the XLA form takes them as any small head;
both against an explicit softmax, values and every argument's gradient,
with a key span and without; an odd head count, a shared key part or values
of another width fall back; the counters say which a program got; heads of
whole lanes lower to the text they lowered to before there were pairs
(``tests/test_afmoe.py`` and ``tests/test_attention_remat.py`` hold the same
from their side: their cases are untouched)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.ops import attention
from fast_autoaugment_tpu.ops.attention import blocked_causal_attention


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(length, heads, dim=64, vdim=64, batch=1, seed=7):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (batch, length, heads, dim)),
            jax.random.normal(keys[1], (batch, length, heads, dim)),
            jax.random.normal(keys[2], (batch, length, heads, vdim)),
            jax.random.normal(keys[3], (batch, length, heads, vdim)))


def _explicit(q, k, v, scale, window=None):
    """The whole ``[T, T]`` score matrix, every head's own keys and values."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    ahead = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None, :]
    seen = (ahead >= 0) & (ahead < (window or q.shape[1]))
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _value_and_grads(fn, q, k, v, cotangent):
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(cotangent * fn(q, k, v)), argnums=(0, 1, 2)))(q, k, v)


def _rise(before: dict, prefix: str) -> dict:
    after = telemetry.registry().counters_snapshot()
    return {key: value - before.get(key, 0.0) for key, value in after.items()
            if key.startswith(prefix) and value != before.get(key, 0.0)}


#: heads, tokens, key span, batch: two and three tiles of 128, a tile of 256;
#: one pair, two and three; spans under a tile, of a tile and between tiles
PAIRED = [(2, 256, None, 1), (4, 384, None, 2), (6, 256, None, 1), (4, 512, None, 1),
          (2, 384, 100, 1), (4, 384, 128, 1), (2, 512, 300, 2)]


@pytest.mark.parametrize("heads, length, window, batch", PAIRED)
def test_heads_of_64_two_to_a_block_are_the_explicit_softmax_forward_and_backward(
        heads, length, window, batch):
    """Every head has keys and values of its own here (no key-value head is
    repeated), so a head that read its neighbour's half of a block would
    show in the output and in all three gradients."""
    q, k, v, cotangent = _inputs(length, heads, batch=batch)
    before = telemetry.registry().counters_snapshot()
    ours, ours_grads = _value_and_grads(
        lambda q, k, v: blocked_causal_attention(q, k, v, scale=0.125, window=window),
        q, k, v, cotangent)
    span = "none" if window is None else str(window)
    assert _rise(before, "faa_attention_head_blocks") == {
        'faa_attention_head_blocks_traced_total{heads_a_block="2"}': 1.0}
    # the label set the other files pin by exact key stays what it was
    assert _rise(before, "faa_attention_cores_traced") == {
        f'faa_attention_cores_traced_total{{form="fused",span="{span}"}}': 1.0}
    whole, whole_grads = _value_and_grads(
        lambda q, k, v: _explicit(q, k, v, 0.125, window), q, k, v, cotangent)
    assert float(ours) == pytest.approx(float(whole), rel=1e-5, abs=1e-4)
    out = blocked_causal_attention(q, k, v, scale=0.125, window=window)
    assert out.shape == v.shape
    assert np.abs(np.asarray(out - _explicit(q, k, v, 0.125, window))).max() <= 2e-6
    for mine, theirs in zip(ours_grads, whole_grads):
        assert np.abs(np.asarray(mine) - np.asarray(theirs)).max() <= 1e-5 * max(
            np.abs(np.asarray(theirs)).max(), 1.0)


@pytest.mark.parametrize("window", [None, 40])
def test_heads_of_64_in_the_xla_form_are_the_explicit_softmax(window):
    """An odd head count falls back to the form every other small head
    takes; nothing is paired and nothing is wrong."""
    q, k, v, cotangent = _inputs(256, 3)
    before = telemetry.registry().counters_snapshot()
    ours, ours_grads = _value_and_grads(
        lambda q, k, v: blocked_causal_attention(q, k, v, scale=0.125, window=window,
                                                 block=64), q, k, v, cotangent)
    span = "none" if window is None else str(window)
    assert _rise(before, "faa_attention_cores_traced") == {
        f'faa_attention_cores_traced_total{{form="blocked_xla",span="{span}"}}': 1.0}
    assert not _rise(before, "faa_attention_head_blocks")
    whole, whole_grads = _value_and_grads(
        lambda q, k, v: _explicit(q, k, v, 0.125, window), q, k, v, cotangent)
    assert float(ours) == pytest.approx(float(whole), rel=1e-5, abs=1e-4)
    for mine, theirs in zip(ours_grads, whole_grads):
        assert np.abs(np.asarray(mine) - np.asarray(theirs)).max() <= 1e-5 * max(
            np.abs(np.asarray(theirs)).max(), 1.0)


@pytest.mark.parametrize("length, heads, dim, vdim, shared, tile, why", [
    (16384, 32, 64, 64, 0, 512, "the configuration's: 16 pairs, 50.3 MB of VMEM"),
    (256, 2, 64, 64, 0, 128, "one pair over two tiles"),
    (256, 3, 64, 64, 0, None, "an odd head count"),
    (256, 4, 64, 64, 64, None, "a width of 64 beside a shared key part"),
    (256, 4, 64, 128, 0, None, "a key of 64 under values of whole lanes"),
    (256, 4, 128, 64, 0, None, "values of 64 under a key of whole lanes"),
    (256, 4, 32, 32, 0, None, "a quarter of a row"),
    (128, 4, 64, 64, 0, None, "a sequence under two tiles"),
    (32768, 32, 64, 64, 0, None, "a pair's whole sequence past the VMEM bound"),
    (256, 4, 128, 128, 0, 128, "heads of whole lanes, as before"),
    (16384, 32, 128, 128, 0, 512, "trinity_mini_train's"),
    (8192, 32, 128, 128, 64, 512, "kimi_linear_48b_a3b_train's"),
])
def test_which_shapes_the_fused_kernels_admit(length, heads, dim, vdim, shared, tile, why):
    """Every head with keys and values of its own (``tests/test_attention_groups.py``
    has the shapes a group of query heads on one key-value head adds)."""
    assert attention._fused_tile(length, heads, 1, dim, vdim, shared) == tile, why


def test_a_pair_of_heads_is_a_block_of_the_arrays_as_they_lie():
    """The kernels' operands and results are ``[B, T, H * 64]``, the arrays'
    own bytes: the jaxpr of the gradient holds no pad, no transpose and no
    concatenate of an array the size of ``q``; the grid runs over pairs, and
    the rows' log-sum-exp keeps ``[B, H, N, tile]``."""
    q, k, v, cotangent = _inputs(256, 4)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(cotangent * blocked_causal_attention(
            q, k, v, scale=0.125)), argnums=(0, 1, 2)))(q, k, v)
    calls, moved = [], []

    def walk(inner):
        for eqn in inner.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
                continue            # the kernels' own bodies work on tiles
            if eqn.primitive.name in ("pad", "transpose", "concatenate"):
                moved.extend(var.aval.shape for var in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(eqn.params["name"] for eqn in calls) == [
        "mla_attention_backward", "mla_attention_forward"]
    for eqn in calls:
        assert eqn.params["grid_mapping"].grid == (1, 2, 2)        # two pairs, two tiles
        shapes = {var.aval.shape for var in list(eqn.invars) + list(eqn.outvars)}
        assert shapes == {(1, 256, 256), (1, 4, 2, 128)}, shapes
    assert not [shape for shape in moved if np.prod(shape) >= q.size], moved


def test_heads_of_whole_lanes_lower_to_the_text_they_lowered_to_without_pairs(monkeypatch):
    """A head of 128 never meets the pairing: with the helpers that stack
    and fold a pair made to raise, forward and backward lower as they do
    with them, character for character."""
    q, k, v, cotangent = _inputs(256, 2, dim=128, vdim=128)

    def lowered():
        jax.clear_caches()
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(cotangent * blocked_causal_attention(
                q, k, v, scale=0.1, window=None)), argnums=(0, 1, 2))).lower(q, k, v).as_text()

    text = lowered()

    def never(*args):
        raise AssertionError("a head of whole lanes met the pairing")

    with monkeypatch.context() as patch:
        for name in ("_stack_pair", "_fold_pair", "_twice", "_low_half"):
            patch.setattr(attention, name, never)
        assert lowered() == text
