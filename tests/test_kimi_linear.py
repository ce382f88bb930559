"""Kimi Linear's three operations against their plain forms, at a small
size on the CPU with seeded random inputs: the chunked delta-rule scan
against the recurrence (output, final state and the gradients of ``q, k,
v, g, beta``), the blocked causal softmax against the whole one, the
expert layer's shares adding up to the uncut layer — and the AdamW the
model trains with against the plain formula."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.models import get_model, model_conf_of
from fast_autoaugment_tpu.models.kimi_linear import KDAMixer
from fast_autoaugment_tpu.ops import kda, moe
from fast_autoaugment_tpu.ops.attention import blocked_causal_attention
from fast_autoaugment_tpu.ops.kda import chunk_kda, recurrent_kda
from fast_autoaugment_tpu.ops.optim import (
    DECAY_MASKS,
    build_optimizer,
    matrices_mask,
)

pytestmark = pytest.mark.usefixtures("highest")


@pytest.fixture()
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, rel):
    scale = float(jnp.abs(b).max())
    assert float(jnp.abs(a - b).max()) <= rel * scale, (
        float(jnp.abs(a - b).max()), scale)


# ------------------------------------------------------------ the KDA scan


#: the tests' own small shape (the ``jnp`` form takes it), and the shape
#: every configuration states, which goes through the fused kernels
#: (interpreted here: the code the chip runs)
SMALL = dict(batch=2, heads=3, kdim=16, vdim=8)
NATIVE = dict(batch=1, heads=2, kdim=128, vdim=128)
#: three heads: the kernels take them one at a time; four: two groups of
#: two, so a group whose lanes are not the block's first
NATIVE_3 = dict(NATIVE, heads=3)
NATIVE_4 = dict(NATIVE, heads=4)

_chunked = jax.jit(chunk_kda, static_argnames="chunk")


def _kda_inputs(length, decay_spread, seed=0, batch=2, heads=3, kdim=16, vdim=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (batch, length, heads, kdim))) * kdim ** -0.5
    k = unit(jax.random.normal(keys[1], (batch, length, heads, kdim)))
    v = jax.random.normal(keys[2], (batch, length, heads, vdim))
    g = -jnp.exp(decay_spread * jax.random.normal(keys[3], (batch, length, heads, kdim)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, length, heads)))
    return q, k, v, g, beta


def _form_counts():
    counters = telemetry.registry().counters_snapshot()
    return {form: counters.get(f'faa_kda_scan_traces_total{{form="{form}"}}', 0.0)
            for form in ("fused", "chunked_xla")}


@pytest.mark.parametrize("length, chunk, shape", [
    (128, 64, SMALL), (96, 32, SMALL), (48, 16, SMALL), (24, 64, SMALL),
    (256, 64, NATIVE), (128, 64, NATIVE_3), (128, 64, NATIVE_4)],
    ids=["128-64", "96-32", "48-16", "24-64", "native", "native-3-heads",
         "native-4-heads"])
def test_chunked_scan_gives_the_recurrences_output_and_state(length, chunk, shape):
    args = _kda_inputs(length, 1.0, **shape)
    out, state = recurrent_kda(*args)
    chunked, chunked_state = _chunked(*args, chunk=chunk)
    _close(chunked, out, 2e-5)
    _close(chunked_state, state, 2e-5)


@pytest.mark.parametrize("shape", [SMALL, NATIVE], ids=["small", "native"])
def test_chunked_scan_takes_an_initial_state(shape):
    args = _kda_inputs(128, 1.0, **shape)
    first = tuple(a[:, :64] for a in args)
    second = tuple(a[:, 64:] for a in args)
    _, carried = _chunked(*first)
    out, state = _chunked(*second, initial_state=carried)
    whole, whole_state = recurrent_kda(*args)
    _close(out, whole[:, 64:], 2e-5)
    _close(state, whole_state, 2e-5)


@pytest.mark.parametrize("length, shape", [(128, SMALL), (256, NATIVE), (128, NATIVE_4)],
                         ids=["small", "native", "native-4-heads"])
def test_a_decay_that_would_overflow_a_factorised_form_does_not(length, shape):
    """A channel that decays by e^-1500 a token: exp(-G) inside a chunk
    is far beyond float32, the recurrence itself is tame."""
    q, k, v, g, beta = _kda_inputs(length, 2.5, seed=3, **shape)
    assert float(g.min()) < -200
    out, state = recurrent_kda(q, k, v, g, beta)
    chunked, chunked_state = _chunked(q, k, v, g, beta)
    assert bool(jnp.isfinite(chunked).all())
    _close(chunked, out, 3e-4)
    _close(chunked_state, state, 3e-4)


_SCAN_ARGUMENTS = ["q", "k", "v", "g", "beta", "initial_state"]


#: (length, shape) of the gradients' inputs
_GRADIENT_SHAPES = {"small": (128, SMALL), "native": (256, NATIVE),
                    "native-3-heads": (128, NATIVE_3),
                    "native-4-heads": (128, NATIVE_4)}


@functools.lru_cache(maxsize=None)
def _scan_gradients(native):
    """``(the recurrence's, the chunked scan's)`` gradients of all six
    arguments, the cotangent on both the output and the final state."""
    length, shape = _GRADIENT_SHAPES[native]
    args = _kda_inputs(length, 1.0, seed=1, **shape)
    batch, _, heads, kdim = args[0].shape
    args += (0.1 * jax.random.normal(jax.random.PRNGKey(9),
                                     (batch, heads, kdim, args[2].shape[-1])),)

    def scalar(fn):
        def f(*a):
            out, state = fn(*a)
            return jnp.sum(out * jnp.cos(out)) + jnp.sum(state ** 2)
        return f

    every = tuple(range(len(_SCAN_ARGUMENTS)))
    return (jax.grad(scalar(recurrent_kda), argnums=every)(*args),
            jax.jit(jax.grad(scalar(chunk_kda), argnums=every))(*args))


@pytest.mark.parametrize("native", list(_GRADIENT_SHAPES))
@pytest.mark.parametrize("argnum, name", list(enumerate(_SCAN_ARGUMENTS)))
def test_chunked_scans_gradient_is_the_recurrences(argnum, name, native):
    plain, chunked = _scan_gradients(native)
    _close(chunked[argnum], plain[argnum], 2e-4)


@pytest.mark.parametrize("shape", [SMALL, NATIVE_4], ids=["small", "native-4-heads"])
def test_heads_side_by_side_are_the_same_scan(shape):
    """``[B, T, H * K]`` as a projection leaves it, in and out: the scan
    of the same numbers cut ``[B, T, H, K]``."""
    q, k, v, g, beta = _kda_inputs(128, 1.0, **shape)
    flat = lambda a: a.reshape(*a.shape[:2], -1)
    out, state = _chunked(q, k, v, g, beta)
    side_by_side, same_state = _chunked(flat(q), flat(k), flat(v), flat(g), beta)
    assert side_by_side.shape == flat(v).shape
    np.testing.assert_array_equal(side_by_side, flat(out))
    np.testing.assert_array_equal(same_state, state)


@pytest.mark.parametrize("shape", [SMALL, NATIVE_4], ids=["small", "native-4-heads"])
def test_unit_length_taken_by_the_scan_is_unit_length_in_front_of_it(shape):
    """``unit_scale``: `q` and `k` of any length go in, and the output,
    the state and the gradients of both are those of the scan given the
    rows brought to unit length (and `q` scaled) beforehand."""
    q, k, v, g, beta = _kda_inputs(128, 1.0, seed=5, **shape)
    q, k = 3.0 * q * (1.0 + beta[..., None]), 0.5 * k * (2.0 - beta[..., None])
    scale = q.shape[-1] ** -0.5

    def scalar(scan):
        def f(q, k):
            out, state = scan(q, k)
            return jnp.sum(out * jnp.cos(out)) + jnp.sum(state ** 2), (out, state)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

    def in_front(q, k):
        return chunk_kda(q * kda.unit_factor(q, scale), k * kda.unit_factor(k),
                         v, g, beta)

    (_, plain), plain_grads = scalar(in_front)(q, k)
    (_, taken), taken_grads = scalar(
        lambda q, k: chunk_kda(q, k, v, g, beta, unit_scale=scale))(q, k)
    for a, b in zip(taken + taken_grads, plain + plain_grads):
        _close(a, b, 2e-5)


def _equations(jaxpr):
    """Every equation of a jaxpr, the programs it calls included but for
    a kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


def test_the_mixer_hands_the_kernels_the_projections_own_layout():
    """The gradient's program of a KDA mixer at the native head shape:
    every array the two kernels read or write along the tokens is ``[B,
    T, H * K]`` as a projection writes it (``beta`` and its gradient
    ``[B, T, H]``), no array of the program is cut to ``[B, T, H, K]``
    and none the size of a projection's output is transposed, on the way
    from a projection to a call or from one back: XLA is asked for no
    tiling that it would have to move such an array to."""
    batch, length, heads, dim, hidden = 1, 192, 4, 128, 32
    mixer = KDAMixer(heads, dim, 4, 1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, length, hidden))
    params = mixer.init(jax.random.PRNGKey(1), x)
    program = jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(mixer.apply(p, x) ** 2), argnums=(0, 1)))(params, x)
    equations = list(_equations(program.jaxpr))
    calls = {eqn.params["name"]: eqn for eqn in equations
             if eqn.primitive.name == "pallas_call"}
    assert sorted(calls) == ["kda_backward", "kda_forward"]
    tokens, gates = (batch, length, heads * dim), (batch, length, heads)

    def along_tokens(variables):
        return sorted(v.aval.shape for v in variables if v.aval.shape[1] == length)

    forward, backward = calls["kda_forward"], calls["kda_backward"]
    assert along_tokens(forward.invars) == [gates] + [tokens] * 4    # q k v g
    assert along_tokens(forward.outvars) == [tokens]
    assert along_tokens(backward.invars) == [gates] + [tokens] * 5   # and do
    assert along_tokens(backward.outvars) == [gates] + [tokens] * 4
    shapes = [v.aval.shape for eqn in equations for v in eqn.outvars
              if hasattr(v.aval, "shape")]
    assert tokens in shapes and (batch, length, heads, dim) not in shapes
    transposed = [eqn.invars[0].aval for eqn in equations
                  if eqn.primitive.name == "transpose"]
    assert transposed and max(a.size for a in transposed) < np.prod(tokens)


def test_a_length_that_is_no_multiple_of_the_chunk_is_refused():
    with pytest.raises(ValueError, match="no multiple"):
        chunk_kda(*_kda_inputs(100, 1.0))


@pytest.mark.parametrize("shape, form", [(SMALL, "chunked_xla"), (NATIVE, "fused")],
                         ids=["small", "native"])
def test_the_scans_counter_counts_the_form_the_trace_took(shape, form):
    args = _kda_inputs(64, 1.0, **shape)
    before = _form_counts()
    jax.eval_shape(chunk_kda, *args)
    after = _form_counts()
    assert {f: after[f] - before[f] for f in after} == {
        f: float(f == form) for f in after}


@pytest.mark.parametrize("ambient, on_tpu, exact", [
    (None, True, False), ("bfloat16", True, False), ("highest", True, True),
    ("float32", True, True), (None, False, True)])
def test_the_kernels_products_take_the_ambient_precision(monkeypatch, ambient,
                                                         on_tpu, exact):
    """bfloat16 operands only where an einsum on this backend would take
    them: on the chip, outside ``default_matmul_precision("highest")``."""
    monkeypatch.setattr(kda, "_on_tpu", lambda: on_tpu)
    with jax.default_matmul_precision(ambient or "default"):
        assert kda._float32_products() is exact


# ------------------------------------------------- the causal softmax


def _whole_softmax(q, k, v, q_shared, k_shared, scale):
    length = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if q_shared is not None:
        scores = scores + jnp.einsum("bqhd,bkd->bhqk", q_shared, k_shared)
    seen = jnp.tril(jnp.ones((length, length), bool))
    weights = jax.nn.softmax(jnp.where(seen, scores * scale, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _attention_inputs(shared, batch=2, length=64, heads=3, dim=8, shared_dim=5, vdim=4):
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    return (jax.random.normal(keys[0], (batch, length, heads, dim)),
            jax.random.normal(keys[1], (batch, length, heads, dim)),
            jax.random.normal(keys[2], (batch, length, heads, vdim)),
            jax.random.normal(keys[3], (batch, length, heads, shared_dim)) if shared else None,
            jax.random.normal(keys[4], (batch, length, shared_dim)) if shared else None)


def _attention_form_counts():
    counters = telemetry.registry().counters_snapshot()
    return {form: counters.get(f'faa_mla_attention_traces_total{{form="{form}"}}', 0.0)
            for form in ("fused", "blocked_xla")}


@pytest.mark.parametrize("block, spans, shared", [
    (8, 4, True), (8, 2, False), (16, 4, True), (64, 4, True), (32, 1, False)])
def test_blocked_attention_is_the_whole_causal_softmax(block, spans, shared):
    q, k, v, q_shared, k_shared = _attention_inputs(shared)

    def blocked(q, k, v):
        return blocked_causal_attention(q, k, v, scale=0.3, q_shared=q_shared,
                                        k_shared=k_shared, block=block, spans=spans)

    before = _attention_form_counts()
    whole = _whole_softmax(q, k, v, q_shared, k_shared, 0.3)
    _close(jax.jit(blocked)(q, k, v), whole, 1e-5)
    after = _attention_form_counts()
    assert after["fused"] == before["fused"]  # a head of 8: the XLA body's
    assert after["blocked_xla"] > before["blocked_xla"]
    plain = jax.grad(lambda *a: jnp.sum(jnp.sin(_whole_softmax(
        *a, q_shared, k_shared, 0.3))), argnums=(0, 1, 2))(q, k, v)
    ours = jax.grad(lambda *a: jnp.sum(jnp.sin(blocked(*a))),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ours, plain):
        _close(a, b, 1e-4)


#: both configurations' head widths (own key, shared key, value), which go
#: through the fused kernels (interpreted here: the code the chip runs).
#: 384 tokens are three tiles of 128: a key tile is the one on the
#: diagonal, one wholly below it, or never met
WIDTHS = {"kimi_linear": dict(dim=128, shared_dim=64, vdim=128),
          "glm4_moe_lite": dict(dim=192, shared_dim=64, vdim=256)}
NATIVE_LENGTH = 384
_ATTENTION_ARGUMENTS = ["q", "k", "v", "q_shared", "k_shared"]
#: how far from the whole float32 softmax: float32 products (what
#: ``highest`` asks) as the small shapes above, bfloat16 operands (the
#: chip's default) inside the tighter of the deployed limits
_ATTENTION_TOLERANCES = {"highest": (1e-5, 1e-4), "default": (0.02, 0.02)}


@functools.lru_cache(maxsize=None)
def _native_attention(family: str, shared: bool, ambient: str):
    """``(ours, the whole softmax's)``, each the output and the gradients
    of every argument there is."""
    widths = WIDTHS[family]
    args = tuple(a for a in _attention_inputs(
        shared, batch=1, length=NATIVE_LENGTH, heads=2, **widths) if a is not None)
    scale = (widths["dim"] + widths["shared_dim"] * shared) ** -0.5

    def ours(q, k, v, q_shared=None, k_shared=None):
        return blocked_causal_attention(q, k, v, scale=scale, q_shared=q_shared,
                                        k_shared=k_shared)

    def whole(q, k, v, q_shared=None, k_shared=None):
        return _whole_softmax(q, k, v, q_shared, k_shared, scale)

    def both(fn):
        every = tuple(range(len(args)))
        return (fn(*args),) + jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=every)(*args)

    # off the chip the ambient precision is float32 whatever it says: the
    # operands are rounded as the chip's default would round them
    exact = {"highest": kda._float32_products, "default": lambda: False}[ambient]
    with pytest.MonkeyPatch.context() as patch, jax.default_matmul_precision("highest"):
        patch.setattr(kda, "_float32_products", exact)
        return both(jax.jit(ours)), both(whole)


@pytest.mark.parametrize("ambient", ["highest", "default"])
@pytest.mark.parametrize("shared", [True, False], ids=["shared", "own"])
@pytest.mark.parametrize("family", sorted(WIDTHS))
def test_fused_attention_is_the_whole_causal_softmax(family, shared, ambient):
    ours, whole = _native_attention(family, shared, ambient)
    _close(ours[0], whole[0], _ATTENTION_TOLERANCES[ambient][0])


@pytest.mark.parametrize("ambient", ["highest", "default"])
@pytest.mark.parametrize("argnum, shared", [
    pytest.param(n, shared, id=f"{name}-{'shared' if shared else 'own'}")
    for shared in (True, False)
    for n, name in enumerate(_ATTENTION_ARGUMENTS[:5 if shared else 3])])
@pytest.mark.parametrize("family", sorted(WIDTHS))
def test_fused_attentions_gradient_is_the_whole_softmaxs(family, argnum, shared, ambient):
    ours, whole = _native_attention(family, shared, ambient)
    _close(ours[1 + argnum], whole[1 + argnum], _ATTENTION_TOLERANCES[ambient][1])


@pytest.mark.parametrize("widths, length, form", [
    (dict(), 64, "blocked_xla"), (WIDTHS["kimi_linear"], 256, "fused"),
    (WIDTHS["glm4_moe_lite"], 1024, "fused"),
    # a sequence under two tiles, and one no tile divides
    (WIDTHS["glm4_moe_lite"], 128, "blocked_xla"),
    (WIDTHS["kimi_linear"], 320, "blocked_xla")],
    ids=["small", "kimi_linear", "glm4_moe_lite", "one-tile", "no-tile"])
def test_the_attentions_counter_counts_the_form_the_trace_took(widths, length, form):
    q, k, v, q_shared, k_shared = _attention_inputs(
        True, batch=1, length=length, heads=2, **widths)
    before = _attention_form_counts()
    jax.eval_shape(functools.partial(blocked_causal_attention, scale=0.1, block=64),
                   q, k, v, q_shared=q_shared, k_shared=k_shared)
    after = _attention_form_counts()
    assert {f: after[f] - before[f] for f in after} == {
        f: float(f == form) for f in after}


def _sizes(jaxpr):
    """The size of every array a jaxpr computes, the programs it calls
    included but for a kernel's body (that is VMEM's)."""
    for eqn in jaxpr.eqns:
        yield from (var.aval.size for var in eqn.outvars if hasattr(var.aval, "size"))
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _sizes(sub)


@pytest.mark.parametrize("family", sorted(WIDTHS))
def test_the_fused_forms_probabilities_never_leave_the_kernels(family):
    """Neither a residual of the ``vjp`` nor any array of the gradient's
    program is as large as one head's scores; the XLA form's are."""
    length, heads = 1024, 2
    args = _attention_inputs(True, batch=1, length=length, heads=heads, **WIDTHS[family])
    scale = 0.1

    def attention(*a):
        return blocked_causal_attention(*a[:3], scale=scale, q_shared=a[3], k_shared=a[4])

    residuals = jax.eval_shape(lambda *a: jax.vjp(attention, *a)[1], *args)
    kept = [leaf.shape for leaf in jax.tree_util.tree_leaves(residuals)]
    assert (1, heads, 2, 512) in kept  # the rows' log-sum-exp, a tile a row
    assert max(np.prod(shape) for shape in kept) == length * heads * 256
    gradient = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(attention(*a)),
                                       argnums=(0, 1, 2, 3, 4)))(*args)
    assert max(_sizes(gradient.jaxpr)) < length * length
    small = _attention_inputs(True, batch=1, length=length, heads=heads)
    blocked = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(attention(*a)),
                                      argnums=(0, 1, 2, 3, 4)))(*small)
    assert max(_sizes(blocked.jaxpr)) >= heads * 512 * length


# ----------------------------------------------------- the expert layer


#: the three token models' expert layers, every width cut: experts, how many
#: a share holds of the shares that divide them, top-k, the scaling and, where
#: an expert is no SwiGLU, its form (``ops/moe.py::FORMS``)
SHARES = {"kimi_linear": dict(experts=16, held=4, top_k=4, scale=2.446),
          "glm4_moe_lite": dict(experts=64, held=8, top_k=4, scale=1.8),
          "nemotron_h": dict(experts=128, held=8, top_k=6, scale=2.5, form="relu2")}


def _moe_inputs(tokens=64, dim=16, width=8, experts=16, top_k=4, seed=4,
                scale=2.446):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (tokens, dim))
    router = jax.random.normal(keys[1], (dim, experts))
    w_gate = jax.random.normal(keys[2], (experts, dim, width)) * dim ** -0.5
    w_up = jax.random.normal(keys[3], (experts, dim, width)) * dim ** -0.5
    w_down = jax.random.normal(keys[4], (experts, width, dim)) * width ** -0.5
    chosen, weights = moe.route(x, router, jnp.zeros(experts), top_k=top_k,
                                scale=scale)
    return x, chosen, weights, w_gate, w_up, w_down


def _every_expert_in_a_loop(x, chosen, weights, w_gate, w_up, w_down,
                            form="swiglu"):
    out = jnp.zeros_like(x)
    for e in range(w_up.shape[0]):
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        hidden = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e]) if form == "swiglu"
                  else jnp.square(jax.nn.relu(x @ w_up[e])))
        out = out + weight[:, None] * (hidden @ w_down[e])
    return out


def test_router_chooses_top_k_of_all_experts_and_renormalises():
    x, chosen, weights, *_ = _moe_inputs()
    assert chosen.shape == weights.shape == (64, 4)
    assert int(chosen.min()) >= 0 and int(chosen.max()) < 16
    assert all(len(set(row)) == 4 for row in np.asarray(chosen))  # distinct
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.446, rtol=1e-5)
    # the bias moves the choice and never the weight
    router = jax.random.normal(jax.random.split(jax.random.PRNGKey(4), 5)[1], (16, 16))
    pushed = jnp.zeros(16).at[7].set(10.0)
    chosen_b, weights_b = moe.route(x, router, pushed, top_k=4, scale=1.0)
    assert bool((chosen_b == 7).any(axis=-1).all())
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, chosen_b, -1)
    np.testing.assert_allclose(np.asarray(weights_b),
                               np.asarray(picked / picked.sum(-1, keepdims=True)),
                               rtol=1e-5)


@pytest.mark.parametrize("family", sorted(SHARES))
def test_the_shares_add_up_to_the_uncut_layer(family):
    """16 experts over 4 shares of 4, 64 over 8 shares of 8, or 128
    two-matrix relu2 experts over 16 shares of 8: the routed parts of all
    the shares are the whole layer's routed part."""
    sizes = SHARES[family]
    experts, held, top_k = sizes["experts"], sizes["held"], sizes["top_k"]
    form = sizes.get("form", "swiglu")
    x, chosen, weights, w_gate, w_up, w_down = _moe_inputs(
        experts=experts, top_k=top_k, scale=sizes["scale"])
    whole = _every_expert_in_a_loop(x, chosen, weights, w_gate, w_up, w_down, form)
    matrices = (w_gate, w_up, w_down) if form == "swiglu" else (w_up, w_down)
    shares = [moe.held_experts(
        x, chosen, weights, *(w[s:s + held] for w in matrices), first=s, form=form)
        for s in range(0, experts, held)]
    assert len(shares) == experts // held
    _close(sum(shares), whole, 1e-5)
    assert all(float(jnp.abs(share).max()) > 0 for share in shares)
    uncut = moe.held_experts(x, chosen, weights, *matrices, first=0, form=form)
    _close(uncut, whole, 1e-5)
    counts = [moe.assignment_counts(chosen, s, held) for s in range(0, experts, held)]
    assert int(sum(c.sum() for c in counts)) == 64 * top_k   # no token dropped


@pytest.mark.parametrize("rows", [3, 16, 64, 512])
def test_the_grouped_blocks_size_changes_no_result(rows):
    """An expert's assignments in blocks of 3 rows (most blocks whole,
    the last in part), of 16, of every token, of more than there are."""
    x, chosen, weights, w_gate, w_up, w_down = _moe_inputs()
    held = jnp.where((chosen >= 4) & (chosen < 12), weights, 0.0)
    plain = _every_expert_in_a_loop(x, chosen - 4, held, w_gate[4:12],
                                    w_up[4:12], w_down[4:12])
    ours = jax.jit(lambda *a: moe.held_experts(*a, first=4, block_rows=rows))(
        x, chosen, weights, w_gate[4:12], w_up[4:12], w_down[4:12])
    _close(ours, plain, 1e-5)


def test_every_token_to_one_expert_is_the_same_sum():
    """The routing a model trained from scratch without the balancing
    rule falls into (PERF.md section 6, PR 35): all tokens choose the
    same experts.  No shape and no result depends on it, and no token is
    dropped: the loop over blocks then runs over every token."""
    x, _, _, w_gate, w_up, w_down = _moe_inputs()
    chosen = jnp.tile(jnp.asarray([[5, 1, 9, 14]], jnp.int32), (64, 1))
    weights = jnp.full((64, 4), 2.446 / 4)
    whole = _every_expert_in_a_loop(x, chosen, weights, w_gate, w_up, w_down)
    parts = [moe.held_experts(x, chosen, weights, w_gate[s:s + 4], w_up[s:s + 4],
                              w_down[s:s + 4], first=s) for s in range(0, 16, 4)]
    _close(sum(parts), whole, 1e-5)
    assert [int(moe.assignment_counts(chosen, s, 4).max()) for s in range(0, 16, 4)] == [
        64, 64, 64, 64]


@pytest.mark.parametrize("rows", [5, 512])
def test_a_held_shares_gradients_are_the_loops(rows):
    """The backward pass ``ops/moe.py`` writes itself (a loop over the
    same blocks) against JAX's own of the plain loop over experts."""
    x, chosen, weights, w_gate, w_up, w_down = _moe_inputs()

    def ours(x, weights, w_gate, w_up, w_down):
        return jnp.sum(jnp.sin(moe.held_experts(
            x, chosen, weights, w_gate, w_up, w_down, first=4, block_rows=rows)))

    def plain(x, weights, w_gate, w_up, w_down):
        held = jnp.where((chosen >= 4) & (chosen < 8), weights, 0.0)
        return jnp.sum(jnp.sin(_every_expert_in_a_loop(
            x, chosen - 4, held, w_gate, w_up, w_down)))

    args = (x, weights, w_gate[4:8], w_up[4:8], w_down[4:8])
    for a, b in zip(jax.jit(jax.grad(ours, argnums=range(5)))(*args),
                    jax.grad(plain, argnums=range(5))(*args)):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("tail", ["no_row_dropped", "part_filled", "every_row_dropped"])
@pytest.mark.parametrize("hidden", [128, 2688, 200])
@pytest.mark.parametrize("rows", [8, 512])
def test_the_combine_kernel_adds_what_the_scatter_adds(rows, hidden, tail):
    """``ops/moe.py::_combine`` (interpreted here) against XLA's
    scatter-add on the same operands, bit for bit: a sum that is not zero
    going in, a block's tokens sorted and distinct, the rows past an
    expert's last assignment named past the last token."""
    tokens = 640
    live = {"no_row_dropped": rows, "part_filled": rows * 5 // 8,
            "every_row_dropped": 0}[tail]
    rng = np.random.default_rng(rows + hidden + live)
    token = tokens + np.arange(rows)
    token[:live] = np.sort(rng.choice(tokens, live, replace=False))
    token = jnp.asarray(token, jnp.int32)
    total = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    block = jnp.asarray(rng.standard_normal((rows, hidden)), jnp.float32)
    scattered = total.at[token].add(block, mode="drop", indices_are_sorted=True,
                                    unique_indices=True)
    combined = jax.jit(moe._combine)(total[:, None], token, block)[:, 0]
    assert np.array_equal(np.asarray(combined), np.asarray(scattered))
    assert np.array_equal(np.asarray(combined[token[:live]]),
                          np.asarray(total[token[:live]] + block[:live]))
    assert (live == 0) == np.array_equal(np.asarray(combined), np.asarray(total))


def _adds_of(x):
    """How a share's loops add a block's rows into a sum of rows like `x`'s."""
    return moe._sum_of_rows(x)[1]


def test_a_sum_of_whole_float32_tiles_goes_through_the_kernel():
    """The choice is the activations' width and type alone: rows of whole
    128-lane tiles of 32-bit words take the kernel (every token cell's:
    2,304, 2,048 and 2,688 wide, float32), anything else XLA's scatter."""
    for width in (128, 2048, 2304, 2688):
        assert _adds_of(jnp.zeros((8, width))) is moe._combine
    assert _adds_of(jnp.zeros((8, 16))) is moe._scatter
    assert _adds_of(jnp.zeros((8, 200))) is moe._scatter
    assert _adds_of(jnp.zeros((8, 128), jnp.bfloat16)) is moe._scatter
    x, chosen, weights, *matrices = _moe_inputs(dim=128)
    traced = str(jax.make_jaxpr(
        lambda x: jax.grad(lambda x: jnp.sum(moe.held_experts(
            x, chosen, weights, *matrices, first=0)))(x))(x))
    # the scatter-adds left are the experts' own gradients, ``d_w.at[expert]``
    assert traced.count("moe_combine") >= 2
    assert "scatter-add" in traced and "f32[64,128] = scatter-add" not in traced


@pytest.mark.parametrize("routing", ["routed", "collapsed"])
@pytest.mark.parametrize("rows", [5, 64, 512])
def test_the_kernels_loops_give_the_plain_loops_sum_and_gradients(rows, routing):
    """The share at a width the kernel takes (128): its sum and every
    gradient against the plain loop over experts, blocks part-filled
    (5 rows), whole, and larger than the tokens; and under the routing of
    a collapsed router, every token to the same held experts."""
    x, chosen, weights, w_gate, w_up, w_down = _moe_inputs(dim=128)
    if routing == "collapsed":
        chosen = jnp.tile(jnp.asarray([[5, 1, 6, 14]], jnp.int32), (64, 1))

    def ours(x, weights, w_gate, w_up, w_down):
        return jnp.sin(moe.held_experts(
            x, chosen, weights, w_gate, w_up, w_down, first=4, block_rows=rows))

    def plain(x, weights, w_gate, w_up, w_down):
        held = jnp.where((chosen >= 4) & (chosen < 8), weights, 0.0)
        return jnp.sin(_every_expert_in_a_loop(
            x, chosen - 4, held, w_gate, w_up, w_down))

    args = (x, weights, w_gate[4:8], w_up[4:8], w_down[4:8])
    _close(jax.jit(ours)(*args), plain(*args), 1e-5)
    for a, b in zip(
            jax.jit(jax.grad(lambda *a: jnp.sum(ours(*a)), argnums=range(5)))(*args),
            jax.grad(lambda *a: jnp.sum(plain(*a)), argnums=range(5))(*args)):
        _close(a, b, 1e-4)


def test_the_bias_moves_towards_the_mean_load_and_balances_a_skewed_router():
    """``b_e += rate * sign(mean load - load_e)``: one step by hand, and
    a router whose scores favour four experts for every token spreads
    its tokens once the rule has run (no gradient involved)."""
    load = jnp.asarray([10, 0, 4, 2], jnp.int32)          # mean 4
    np.testing.assert_allclose(
        np.asarray(moe.balance_bias(jnp.asarray([0.5, 0.0, -1.0, 0.0]), load, 0.1)),
        [0.4, 0.1, -1.0, 0.1], rtol=1e-6)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(keys[0], (256, 16))
    # a component every token shares, as a freshly initialised model's
    # residual stream has, decides the choice until the bias answers it
    x = x * 0.3 + jax.random.normal(keys[1], (1, 16))
    router = jax.random.normal(keys[2], (16, 16)) * 0.5
    bias = jnp.zeros(16)

    def loads(bias):
        chosen, _ = moe.route(x, router, bias, top_k=4, scale=1.0)
        return moe.assignment_counts(chosen, 0, 16)

    before = loads(bias)
    for _ in range(200):
        bias = moe.balance_bias(bias, loads(bias), 0.01)
    after = loads(bias)
    mean = 256 * 4 / 16
    assert int(before.max()) > 3 * mean and int(before.min()) < mean / 8
    assert int(after.max()) < 2 * mean and int(after.min()) > mean / 2
    assert int(after.sum()) == int(before.sum()) == 256 * 4


def _tiny_layer_conf(held, share, family="kimi_linear"):
    if family == "nemotron_h":
        return {
            "model": {
                "type": "nemotron_h", "remat": False, "hidden_size": 32,
                "hybrid_override_pattern": "ME", "num_hidden_layers": 2,
                "layer_norm_epsilon": 1e-5, "mamba_num_heads": 2,
                "mamba_head_dim": 8, "n_groups": 1, "ssm_state_size": 8,
                "conv_kernel": 4, "chunk_size": 8, "num_attention_heads": 2,
                "num_key_value_heads": 1, "head_dim": 8, "n_routed_experts": 128,
                "num_experts_per_tok": 6, "moe_intermediate_size": 16,
                "moe_shared_expert_intermediate_size": 24, "n_shared_experts": 1,
                "mlp_hidden_act": "relu2", "n_group": 1, "topk_group": 1,
                "norm_topk_prob": True, "routed_scaling_factor": 2.5,
                "vocab_size": 32, "expert_share": share},
            "dataset": "synthetic_tokens", "experts_held": held}
    if family == "glm4_moe_lite":
        return {
            "model": {
                "type": "glm4_moe_lite", "remat": False, "first_k_dense_replace": 1,
                "hidden_size": 32, "intermediate_size": 48, "kv_lora_rank": 8,
                "q_lora_rank": 12, "moe_intermediate_size": 16,
                "norm_topk_prob": True, "topk_method": "noaux_tc",
                "num_attention_heads": 2, "n_group": 1, "n_routed_experts": 64,
                "num_experts_per_tok": 4, "num_hidden_layers": 2,
                "n_shared_experts": 1, "num_nextn_predict_layers": 1,
                "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "rms_norm_eps": 1e-5,
                "rope_theta": 1e6, "routed_scaling_factor": 1.8, "topk_group": 1,
                "v_head_dim": 8, "vocab_size": 32, "expert_share": share},
            "dataset": "synthetic_tokens", "experts_held": held}
    return {
        "model": {
            "type": "kimi_linear", "remat": False, "first_k_dense_replace": 1,
            "hidden_size": 32, "intermediate_size": 48, "kv_lora_rank": 8,
            "linear_attn_config": {"full_attn_layers": [4], "head_dim": 8,
                                   "kda_layers": [1, 2, 3], "num_heads": 2,
                                   "short_conv_kernel_size": 4},
            "mla_use_nope": True, "moe_intermediate_size": 16, "moe_layer_freq": 1,
            "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
            "num_attention_heads": 2, "num_expert_group": 1, "num_experts": 16,
            "num_experts_per_token": 4, "num_hidden_layers": 2,
            "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 8,
            "qk_rope_head_dim": 4, "rms_norm_eps": 1e-5,
            "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 8,
            "vocab_size": 32, "expert_share": share},
        "dataset": "synthetic_tokens", "experts_held": held}


@pytest.mark.parametrize("family", sorted(SHARES))
def test_the_models_expert_layer_shares_add_up_with_the_shared_expert_once(family):
    """Through the module: layer 2 of a two-layer model, one share after
    another with the uncut model's weights (4 shares of 4 of 16 experts,
    the 8 shares of 8 of 64, or the 16 shares of 8 of 128 relu2 experts
    beside a shared expert of a width of its own); the routed parts plus
    the shared expert's, counted once, are the uncut layer's output — the
    module's, and the uncut layer of that family's plain reference."""
    from benchmarks.harness import spec
    from fast_autoaugment_tpu.models.token_blocks import FEED_FORWARDS, ExpertLayer

    sizes = SHARES[family]
    experts, held = sizes["experts"], sizes["held"]
    form = sizes.get("form", "swiglu")
    feed_forward, (names, _) = FEED_FORWARDS[form], moe.FORMS[form]
    # the third family's shared expert is wider than its routed ones
    shared_width = 24 if family == "nemotron_h" else None
    whole = get_model(model_conf_of(_tiny_layer_conf(experts, 0, family)), 32)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, 32)
    params = whole.init({"params": jax.random.PRNGKey(6)}, ids)["params"]
    layer = params["layer2"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 32))

    def apply(held, share, shared, p):
        module = ExpertLayer(experts=experts, held=held, share=share,
                             top_k=sizes["top_k"], width=16, shared=shared,
                             scale=sizes["scale"], renormalize=True, form=form,
                             shared_width=shared_width)
        return module.apply({"params": p}, x)

    uncut = apply(experts, 0, 1, layer)
    routed = dict(layer)
    shared_expert = routed.pop("shared_experts")
    parts = []
    for share in range(experts // held):
        own = dict(routed, **{k: routed[k][held * share:held * (share + 1)]
                              for k in (f"experts_{name}" for name in names)})
        parts.append(apply(held, share, 0, own))
    once = feed_forward(shared_width or 16).apply({"params": shared_expert}, x)
    assert shared_expert["up_proj"]["kernel"].shape == (32, shared_width or 16)
    _close(sum(parts) + once, uncut, 1e-5)
    reference = spec.load_module("references", family)
    plain, _ = reference._experts(
        x.reshape(32, 32), layer, {"top_k": sizes["top_k"], "renormalize": True,
                                   "routed_scale": sizes["scale"]})
    _close((sum(parts) + once).reshape(32, 32), plain, 1e-5)
    # and the model is told which experts it holds: a share's parameters
    cut = get_model(model_conf_of(_tiny_layer_conf(held, 2, family)), 32)
    shapes = jax.eval_shape(lambda: cut.init({"params": jax.random.PRNGKey(6)}, ids))
    assert shapes["params"]["layer2"]["moe"]["experts_up"].shape == (held, 32, 16)
    assert ("experts_gate" in shapes["params"]["layer2"]["moe"]) == (form == "swiglu")
    assert shapes["params"]["layer2"]["moe"]["router"].shape == (32, experts)


@pytest.mark.parametrize("bad", [
    {"experts_held": 5}, {"experts_held": 4, "expert_share": 4},
    {"layers_held": 3}, {"ids_held": 64}])
def test_a_share_the_model_cannot_hold_is_refused(bad):
    conf = _tiny_layer_conf(16, 0)
    conf.pop("experts_held")
    conf["model"]["expert_share"] = bad.pop("expert_share", 0)
    conf.update(bad)
    with pytest.raises(ValueError):
        get_model(model_conf_of(conf), 32)


# ------------------------------------------------------------------ AdamW

ADAMW = {"type": "adamw", "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
         "decay": 0.1, "clip": 1.0}


def _params_and_grads():
    keys = jax.random.split(jax.random.PRNGKey(8), 8)
    shapes = {"proj": {"kernel": (6, 5)}, "experts_up": (3, 5, 4),
              "conv": {"kernel": (4, 6)}, "norm": {"weight": (6,)},
              "A_log": (3,), "dt_bias": (2, 3),
              "e_score_correction_bias": (8,), "embed_tokens": (9, 6)}
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    params = jax.tree.unflatten(treedef, [
        jax.random.normal(k, s) for k, s in zip(keys, leaves)])
    grads = jax.tree.map(lambda p: 3.0 * jnp.cos(p), params)
    return params, grads


def _plain_adamw(params, grads, steps, lr, *, decay=True, second_moment=True):
    """AdamW by the formula, the global-norm clip first, the decay
    decoupled and on leaves of two or more dimensions but for ``dt_bias``."""
    decayed = matrices_mask(params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    for t in range(1, steps + 1):
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        clipped = jax.tree.map(lambda g: g * jnp.minimum(1.0, 1.0 / norm), grads)
        m = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, m, clipped)
        v = jax.tree.map(lambda v, g: 0.95 * v + 0.05 * g * g, v, clipped)

        def update(p, m, v, d):
            m_hat = m / (1 - 0.9 ** t)
            v_hat = v / (1 - 0.95 ** t) if second_moment else jnp.ones_like(v)
            step = m_hat / (jnp.sqrt(v_hat) + 1e-8)
            return p - lr * (step + (0.1 * p if decay and d else 0.0))

        params = jax.tree.map(update, params, m, v, decayed)
    return params


def _optimizer_steps(params, grads, steps, lr):
    optimizer = build_optimizer(ADAMW, lambda step: lr)
    state = optimizer.init(params)
    for _ in range(steps):
        updates, state = optimizer.update(grads, state, params)
        params = optax.apply_updates(params, updates)
    return params


def test_adamw_step_is_the_plain_formula_with_the_decay_mask():
    params, grads = _params_and_grads()
    ours = _optimizer_steps(params, grads, 3, 1e-2)
    plain = _plain_adamw(params, grads, 3, 1e-2)
    for a, b, p in zip(jax.tree.leaves(ours), jax.tree.leaves(plain),
                       jax.tree.leaves(params)):
        _close(a - p, b - p, 1e-5)


@pytest.mark.parametrize("left_out", ["decay", "second_moment"])
def test_adamw_with_a_part_left_out_is_told_apart(left_out):
    params, grads = _params_and_grads()
    ours = _optimizer_steps(params, grads, 3, 1e-2)
    wrong = _plain_adamw(params, grads, 3, 1e-2, **{left_out: False})
    gaps = [float(jnp.abs(a - b).max() / jnp.abs(a - p).max())
            for a, b, p in zip(jax.tree.leaves(ours), jax.tree.leaves(wrong),
                               jax.tree.leaves(params))]
    assert max(gaps) > 0.05, gaps


def test_decay_mask_takes_matrices_and_leaves_the_named_vectors():
    params, _ = _params_and_grads()
    mask = matrices_mask(params)
    assert mask == {"proj": {"kernel": True}, "experts_up": True,
                    "conv": {"kernel": True}, "norm": {"weight": False},
                    "A_log": False, "dt_bias": False,
                    "e_score_correction_bias": False, "embed_tokens": True}
    assert set(DECAY_MASKS) == {"non_bn", "matrices"}


def test_unknown_decay_mask_and_optimizer_type_are_refused():
    with pytest.raises(ValueError, match="decay_mask"):
        build_optimizer(dict(ADAMW, decay_mask="everything"), lambda s: 1e-3)
    with pytest.raises(ValueError, match="decay_mask"):
        build_optimizer({"type": "sgd", "decay": 1e-4, "decay_mask": "bn"},
                        lambda s: 1e-3)
    with pytest.raises(ValueError, match="optimizer type"):
        build_optimizer({"type": "lion"}, lambda s: 1e-3)


def test_sgd_keeps_its_non_bn_mask_by_default():
    """The image confs name no mask: the chain they get is the one they had."""
    params = {"conv": {"kernel": jnp.ones((3, 3))}, "bn1": {"scale": jnp.ones((3,))}}
    optimizer = build_optimizer({"type": "sgd", "decay": 0.5, "clip": 0, "momentum": 0.0,
                                 "nesterov": False}, lambda s: 1.0)
    zeros = jax.tree.map(jnp.zeros_like, params)
    updates, _ = optimizer.update(zeros, optimizer.init(params), params)
    assert float(jnp.abs(updates["bn1"]["scale"]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(updates["conv"]["kernel"]), -0.5)


# ------------------------------------------- the checkpoint written in pieces


@pytest.mark.parametrize("case", ["train_state", "mixed_leaves", "small_only"])
def test_streamed_checkpoint_payload_is_flax_to_bytes_byte_for_byte(case, tmp_path):
    """``core/checkpoint.py::_stream_payload`` against
    ``flax.serialization.to_bytes``: the same bytes, digest and size, for a
    train state with AdamW's moments (large float32 leaves, a step
    counter, empty collections), for leaves of other kinds and sizes
    around the streaming threshold, and for a state with no large leaf."""
    import hashlib
    import io

    from flax import serialization

    from fast_autoaugment_tpu.core import checkpoint
    from fast_autoaugment_tpu.train.steps import create_train_state

    if case == "train_state":
        conf = _tiny_layer_conf(4, 1)
        conf["model"].update(hidden_size=128, vocab_size=256)
        model = get_model(model_conf_of(conf), 256)
        state = create_train_state(
            model, build_optimizer(ADAMW, lambda step: 1e-3),
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), use_ema=True,
            jit_init=True)
        assert max(leaf.nbytes for leaf in jax.tree.leaves(state)) >= 1 << 16
    elif case == "mixed_leaves":
        rng = np.random.default_rng(0)
        state = {"f32": jnp.asarray(rng.normal(size=(300, 70)), jnp.float32),
                 "bf16": jnp.asarray(rng.normal(size=(40000,)), jnp.bfloat16),
                 "i8_on_the_threshold": np.arange(1 << 16, dtype=np.int8),
                 "one_under": np.zeros((1 << 16) - 1, np.uint8),
                 "strided": np.arange(90000, dtype=np.int32).reshape(300, 300).T,
                 "nested": {"empty": {}, "scalar": np.float32(2.5), "int": 7,
                            "none": None, "text": "ids", "zero_d": jnp.int32(3),
                            "no_elements": np.zeros((0, 4), np.float32)},
                 "tuple_of_states": (optax.EmptyState(), {"mu": jnp.ones((200, 200))})}
    else:
        state = {"w": jnp.ones((4, 4)), "step": jnp.int32(9)}
    want = serialization.to_bytes(state)
    got = io.BytesIO()
    digest, size = checkpoint._stream_payload(state, got)
    assert got.getvalue() == want
    assert (digest, size) == (hashlib.sha256(want).hexdigest(), len(want))
    # and through the public pair: what is saved restores to the same leaves
    path = str(tmp_path / "state.msgpack")
    checkpoint.save_checkpoint(path, state, {"epoch": 1})
    with open(path, "rb") as fh:
        assert fh.read() == want
    assert checkpoint.read_metadata(path)["digest"] == digest
    restored = checkpoint.load_checkpoint(path, state)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
