"""Grouped-query attention in ``ops/attention.py``: `k` and `v` come with
fewer heads than `q`, and the fused kernels (interpreted here, the code the
chip runs) give a group of query heads its key-value head by index map —
nothing is repeated in HBM, and the backward kernel sums a group's ``dk``,
``dv`` in VMEM.  Against an explicit softmax on repeated heads, values and
every argument's gradient, at groups of 1, 4 and 8, with a key span and
without, heads of 128 and paired heads of 64 (which are still repeated in
front of the kernels), as ``[B, T, H, D]`` and as the heads side by side
(``heads=``); the forward output bit for bit the repeat's; the counters the
mapping brings; which shapes are admitted; every head with keys and values
of its own lowering as it did."""

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


def _inputs(length, heads, kv_heads, dim, batch=1, seed=11):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (batch, length, heads, dim)),
            jax.random.normal(keys[1], (batch, length, kv_heads, dim)),
            jax.random.normal(keys[2], (batch, length, kv_heads, dim)),
            jax.random.normal(keys[3], (batch, length, heads, dim)))


def _repeated(q, k, v):
    group = q.shape[2] // k.shape[2]
    return q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def _explicit(q, k, v, scale, window=None):
    """The whole ``[T, T]`` score matrix on the key-value heads repeated to
    every query head: the repeat's transpose sums a group's gradient."""
    q, k, v = _repeated(q, k, v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    ahead = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None, :]
    seen = (ahead >= 0) & (ahead < (window or q.shape[1]))
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _side_by_side(fn, heads):
    """`fn` handed the heads side by side, ``[B, T, H * D]``, and its
    result cut back into heads."""
    def wrapped(q, k, v):
        flat = lambda a: a.reshape(a.shape[0], a.shape[1], -1)
        return fn(flat(q), flat(k), flat(v), heads=heads).reshape(q.shape)
    return wrapped


def _value_and_grads(fn, q, k, v, cotangent):
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(cotangent * fn(q, k, v)), argnums=(0, 1, 2)))(q, k, v)


def _rise(before: dict, prefix: str) -> dict:
    after = telemetry.registry().counters_snapshot()
    return {key: value - before.get(key, 0.0) for key, value in after.items()
            if key.startswith(prefix) and value != before.get(key, 0.0)}


#: query heads, key-value heads, head width, tokens, key span, batch, rows
GROUPED = [
    (4, 4, 128, 256, None, 1, False), (4, 4, 128, 256, None, 1, True),
    (4, 1, 128, 256, None, 1, False), (4, 1, 128, 384, 100, 2, True),
    (8, 2, 128, 256, 128, 1, True), (8, 1, 128, 256, None, 1, True),
    (16, 2, 128, 256, 300, 1, False),
    (4, 1, 64, 256, None, 1, True), (8, 2, 64, 384, 100, 1, False),
    (8, 1, 64, 256, None, 2, True), (4, 4, 64, 256, 128, 1, True),
]


@pytest.mark.parametrize("heads, kv_heads, dim, length, window, batch, rows", GROUPED)
def test_a_group_of_query_heads_on_one_key_value_head_is_the_explicit_softmax(
        heads, kv_heads, dim, length, window, batch, rows):
    """Value and the three gradients; ``dk`` and ``dv`` are a group's sums,
    which no repeat is differentiated for."""
    q, k, v, cotangent = _inputs(length, heads, kv_heads, dim, batch)
    core = lambda q, k, v, **kw: blocked_causal_attention(
        q, k, v, scale=0.125, window=window, **kw)
    ours, ours_grads = _value_and_grads(
        _side_by_side(core, heads) if rows else core, q, k, v, cotangent)
    whole, whole_grads = _value_and_grads(
        lambda q, k, v: _explicit(q, k, v, 0.125, window), q, k, v, cotangent)
    assert float(ours) == pytest.approx(float(whole), rel=1e-5, abs=1e-4)
    assert [g.shape for g in ours_grads] == [q.shape, k.shape, v.shape]
    for mine, theirs in zip(ours_grads, whole_grads):
        assert np.abs(np.asarray(mine) - np.asarray(theirs)).max() <= 1e-5 * max(
            np.abs(np.asarray(theirs)).max(), 1.0)


@pytest.mark.parametrize("heads, kv_heads, dim, window", [
    (4, 1, 128, None), (8, 2, 128, 100), (8, 1, 128, 128), (4, 1, 64, None), (8, 2, 64, 100)])
def test_the_forward_output_is_the_repeats_bit_for_bit(heads, kv_heads, dim, window):
    """The same tiles, loops and order: a group's head by index map reads
    the bytes a repeat would have copied, as ``[B, T, H, D]`` and as the
    heads side by side."""
    q, k, v, _ = _inputs(384, heads, kv_heads, dim, batch=2)
    core = lambda q, k, v, **kw: blocked_causal_attention(
        q, k, v, scale=0.1, window=window, **kw)
    repeated = core(*_repeated(q, k, v))
    assert np.array_equal(np.asarray(core(q, k, v)), np.asarray(repeated))
    assert np.array_equal(np.asarray(_side_by_side(core, heads)(q, k, v)),
                          np.asarray(repeated))


def test_the_gradients_are_the_repeats_but_for_the_order_of_a_groups_sum():
    """``dq`` bit for bit; ``dk``, ``dv`` the sum over the group of what the
    kernels give every query head of repeated heads, in another order."""
    q, k, v, cotangent = _inputs(256, 8, 2, 128)
    core = lambda q, k, v: blocked_causal_attention(q, k, v, scale=0.1)
    _, mapped = _value_and_grads(core, q, k, v, cotangent)
    _, repeated = _value_and_grads(core, *_repeated(q, k, v), cotangent)
    assert np.array_equal(np.asarray(mapped[0]), np.asarray(repeated[0]))
    for mine, theirs in zip(mapped[1:], repeated[1:]):
        summed = np.asarray(theirs).reshape(1, 256, 2, 4, 128).sum(3)
        np.testing.assert_allclose(np.asarray(mine), summed, rtol=0, atol=1e-5)


@pytest.mark.parametrize("heads, kv_heads, dim, group, saved", [
    (8, 1, 128, "8", 8 * 2 * 256 * 128 * 4), (8, 2, 128, "4", 4 * 2 * 256 * 256 * 4),
    (4, 4, 128, "1", 0), (8, 2, 64, "1", 0)])
def test_the_counters_say_what_the_kernels_mapped_and_what_no_repeat_wrote(
        heads, kv_heads, dim, group, saved):
    """A paired core (heads of 64) is still handed repeated heads: its
    kernels map every head to its own, and nothing is saved."""
    q, k, v, _ = _inputs(256, heads, kv_heads, dim)
    before = telemetry.registry().counters_snapshot()
    jax.eval_shape(lambda q, k, v: blocked_causal_attention(q, k, v, scale=0.1), q, k, v)
    assert _rise(before, "faa_attention_kv_heads_mapped") == {
        f'faa_attention_kv_heads_mapped_total{{group="{group}"}}': 1.0}
    assert _rise(before, "faa_attention_kv_repeat_bytes_saved") == (
        {"faa_attention_kv_repeat_bytes_saved_total": float(saved)} if saved else {})
    assert _rise(before, "faa_attention_cores_traced") == {
        'faa_attention_cores_traced_total{form="fused",span="none"}': 1.0}


@pytest.mark.parametrize("window", [None, 40])
def test_grouped_heads_in_the_xla_form_are_the_explicit_softmax(window):
    """Heads of 8, which the kernels do not take: the key-value heads are
    repeated in front of the XLA form, side by side or not."""
    q, k, v, cotangent = _inputs(128, 4, 2, 8)
    core = lambda q, k, v, **kw: blocked_causal_attention(
        q, k, v, scale=0.3, window=window, block=32, **kw)
    before = telemetry.registry().counters_snapshot()
    ours, ours_grads = _value_and_grads(core, q, k, v, cotangent)
    rows, rows_grads = _value_and_grads(_side_by_side(core, 4), q, k, v, cotangent)
    assert _rise(before, "faa_attention_cores_traced") == {
        'faa_attention_cores_traced_total{form="blocked_xla",span="'
        f'{"none" if window is None else window}"}}': 2.0}
    assert not _rise(before, "faa_attention_kv_heads_mapped")
    whole, whole_grads = _value_and_grads(
        lambda q, k, v: _explicit(q, k, v, 0.3, window), q, k, v, cotangent)
    assert float(ours) == pytest.approx(float(whole), rel=1e-5, abs=1e-4)
    assert float(rows) == float(ours)
    for mine, side, theirs in zip(ours_grads, rows_grads, whole_grads):
        assert np.array_equal(np.asarray(mine), np.asarray(side))
        assert np.abs(np.asarray(mine) - np.asarray(theirs)).max() <= 1e-5 * max(
            np.abs(np.asarray(theirs)).max(), 1.0)


@pytest.mark.parametrize("length, heads, group, dim, vdim, shared, tile, why", [
    (16384, 32, 8, 128, 128, 0, 512, "trinity_mini_train's: 50.3 + 16.8 MB of VMEM"),
    (8192, 32, 16, 128, 128, 0, 512, "nemotron3_nano_30b_a3b_train's"),
    (16384, 32, 4, 64, 64, 0, 512, "lfm2_8b_a1b_train's: pairs, their heads repeated"),
    (19456, 32, 1, 128, 128, 0, 512, "every head its own: 59.8 MB"),
    (19456, 32, 8, 128, 128, 0, None, "a group's sums beside it: 79.7 MB, past the bound"),
    (256, 8, 2, 128, 128, 64, None, "a shared key part beside grouped heads"),
    (256, 8, 2, 256, 128, 0, 128, "a key wider than the values"),
    (128, 8, 2, 128, 128, 0, None, "a sequence under two tiles"),
])
def test_which_grouped_shapes_the_fused_kernels_admit(length, heads, group, dim, vdim, shared,
                                                      tile, why):
    assert attention._fused_tile(length, heads, group, dim, vdim, shared) == tile, why


def test_heads_that_are_no_whole_group_are_refused():
    q, k, v, _ = _inputs(256, 6, 4, 128)
    with pytest.raises(ValueError, match="no whole number of query heads"):
        blocked_causal_attention(q, k, v, scale=0.1)
    flat = lambda a: a.reshape(1, 256, -1)
    with pytest.raises(ValueError, match="no whole number of query heads"):
        blocked_causal_attention(flat(q), flat(k), flat(v), scale=0.1, heads=6)
    with pytest.raises(ValueError, match="no shared key part"):
        blocked_causal_attention(flat(q), flat(q), flat(q), scale=0.1, heads=6,
                                 q_shared=q, k_shared=q[:, :, 0])


def _calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, by kernel name."""
    found = {}

    def walk(inner):
        for eqn in inner.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


def _gradient_blocks(backward):
    """The blocks of a backward kernel's last two results, ``dk`` and ``dv``."""
    blocks = [spec.block_shape for spec in backward.params["grid_mapping"].block_mappings]
    return [tuple(int(getattr(d, "block_size", d)) for d in b) for b in blocks[-2:]]


def test_a_group_is_an_index_map_over_the_arrays_as_they_lie():
    """The kernels' operands are `k` and `v` at their own width, ``[B, T, G
    * D]``; the backward kernel's ``dk``, ``dv`` come back at that width, a
    key-value head's whole sequence a block; and the gradient's jaxpr holds
    no broadcast, concatenate, pad or transpose of an array the size of
    `q`."""
    q, k, v, cotangent = _inputs(256, 8, 2, 128)
    flat = lambda a: a.reshape(1, 256, -1)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flat(cotangent) * blocked_causal_attention(
            q, k, v, scale=0.125, heads=8)), argnums=(0, 1, 2)))(flat(q), flat(k), flat(v))
    calls = _calls(jaxpr)
    assert sorted(calls) == ["mla_attention_backward", "mla_attention_forward"]
    forward, backward = calls["mla_attention_forward"], calls["mla_attention_backward"]
    assert [var.aval.shape for var in forward.invars] == [
        (1, 256, 1024), (1, 256, 256), (1, 256, 256)]
    assert [var.aval.shape for var in backward.outvars] == [
        (1, 256, 1024), (1, 256, 256), (1, 256, 256)]
    assert _gradient_blocks(backward) == [(1, 256, 128), (1, 256, 128)]
    moved = []

    def walk(inner):
        for eqn in inner.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            scalar = eqn.invars and eqn.invars[0].aval.shape == ()   # this test's own cotangent
            if eqn.primitive.name in ("broadcast_in_dim", "concatenate", "pad",
                                      "transpose") and not scalar:
                moved.extend(var.aval.shape for var in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert not [shape for shape in moved if np.prod(shape) >= q.size], moved


def test_every_head_with_its_own_keys_lowers_as_it_did_without_groups(monkeypatch):
    """``heads == kv_heads`` never meets the grouping: with what only a
    group uses made to raise (the one-buffer block of a group's sums, the
    repeat in front of the XLA form and of pairs), forward and backward
    lower as they do with it, character for character; the grid's head
    axis stays parallel and ``dk``, ``dv`` a head's tile a block."""
    q, k, v, cotangent = _inputs(256, 2, 2, 128)

    def gradient(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(cotangent * blocked_causal_attention(
            q, k, v, scale=0.1, window=None)), argnums=(0, 1, 2))(q, k, v)

    def lowered():
        jax.clear_caches()
        return jax.jit(gradient).lower(q, k, v).as_text()

    text = lowered()

    def never(*args, **kwargs):
        raise AssertionError("every head has its own keys, and met the grouping")

    with monkeypatch.context() as patch:
        patch.setattr(attention.pl, "Buffered", never)
        patch.setattr(attention.jnp, "repeat", never)
        assert lowered() == text
    backward = _calls(jax.make_jaxpr(gradient)(q, k, v))["mla_attention_backward"]
    assert _gradient_blocks(backward) == [(1, 128, 128), (1, 128, 128)]
    semantics = backward.params["compiler_params"]["mosaic_tpu"].dimension_semantics
    assert [str(s).split(".")[-1].lower() for s in semantics] == [
        "parallel", "parallel", "arbitrary"]


# ----------------------------------------------------- the mixer on the rows

def _mixer_by_hand(params, x, heads, kv_heads, dim, eps, theta, window, gated):
    """``GQAMixer``'s equations on ``[B, T, H, D]`` arrays, the key-value
    heads repeated and the whole score matrix."""
    from fast_autoaugment_tpu.models.token_blocks import rotate_by_position

    batch, length, _ = x.shape
    q, k, v = (x @ params[f"{name}_proj"]["kernel"] for name in "qkv")
    q, k, v = (a.reshape(batch, length, n, dim) for a, n in (
        (q, heads), (k, kv_heads), (v, kv_heads)))
    if eps is not None:
        normed = lambda a, w: a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps) * w
        q = normed(q, params["q_norm"]["weight"])
        k = normed(k, params["k_norm"]["weight"])
    if theta is not None:
        q, k = (rotate_by_position(a, theta, "halves") for a in (q, k))
    out = _explicit(q, k, v, dim ** -0.5, window).reshape(batch, length, heads * dim)
    if gated:
        out = out * jax.nn.sigmoid(x @ params["gate_proj"]["kernel"])
    return out @ params["o_proj"]["kernel"]


@pytest.mark.parametrize("parts", [
    dict(qk_norm_eps=1e-5, rope_theta=10000.0, window=100, gated=True),
    dict(qk_norm_eps=1e-5, gated=True), dict(rope_theta=100.0), dict()],
    ids=["afmoe_window", "afmoe_full", "rotary_alone", "bare"])
@pytest.mark.parametrize("heads, kv_heads, dim", [(4, 1, 128), (4, 2, 64)],
                         ids=["rows_of_whole_lanes", "heads_of_64"])
def test_the_mixer_on_the_projections_rows_is_the_mixer_by_hand(heads, kv_heads, dim, parts):
    """A head of whole lanes: every array from ``q_proj`` to ``o_proj`` the
    heads side by side, the norm and the rotation over the array cut as it
    is tiled (positions by token, not by row of the cut), the key-value
    heads unrepeated.  A head of 64: ``[B, T, H, 64]`` and the repeat, as
    before.  Both the equations by hand, value and every gradient."""
    from fast_autoaugment_tpu.models.token_blocks import GQAMixer

    x = jax.random.normal(jax.random.PRNGKey(5), (2, 256, 96))
    mixer = GQAMixer(heads, kv_heads, dim, **parts)
    params = mixer.init(jax.random.PRNGKey(0), x)["params"]
    # weights far from their initial values, the norms' among them
    params = jax.tree.map(lambda a: a + 0.3 * jax.random.normal(
        jax.random.PRNGKey(a.size), a.shape) * jnp.abs(a).mean(), params)
    cotangent = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    ours, ours_grads = jax.value_and_grad(
        lambda p, x: jnp.sum(cotangent * mixer.apply({"params": p}, x)), (0, 1))(params, x)
    theirs, their_grads = jax.value_and_grad(
        lambda p, x: jnp.sum(cotangent * _mixer_by_hand(
            p, x, heads, kv_heads, dim, parts.get("qk_norm_eps"), parts.get("rope_theta"),
            parts.get("window"), parts.get("gated", False))), (0, 1))(params, x)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-4, abs=1e-4)
    for mine, hand in zip(jax.tree.leaves(ours_grads), jax.tree.leaves(their_grads)):
        assert np.abs(np.asarray(mine) - np.asarray(hand)).max() <= 2e-5 * max(
            np.abs(np.asarray(hand)).max(), 1.0)
