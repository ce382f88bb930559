"""``MLAMixer`` on the projections' own rows (``models/token_blocks.py``,
``ops/mlarows.py``, ``ops/attention.py``'s ``heads=`` / ``k_shared=`` /
``theta=``): at GLM-4.7-Flash's head shape (192 + 64 on values of 256) no
array is cut into heads between the projections and ``o_proj`` — the shared
key part, the rotary pairs and the rounding are one pass over the rows, the
kernels interpreted here (the code the chip runs).  Against the cut path —
the parent's ``MLAMixer`` arithmetic, kept below as the plain form — value
and every parameter's gradient; the parameter tree; what the gradient's
jaxpr moves; the row pass against jnp; the shapes that keep the cut path;
the counter; and ``GQAMixer``'s lowering, which none of this touches."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from flax import linen as nn

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.models import get_model, model_conf_of, token_blocks
from fast_autoaugment_tpu.models.token_blocks import (
    GQAMixer,
    MLAMixer,
    key_value_columns,
    rotate_by_position,
)
from fast_autoaugment_tpu.ops import attention, mlarows
from fast_autoaugment_tpu.ops.attention import blocked_causal_attention

#: GLM-4.7-Flash's head, two of them, on a sequence of two tiles
GLM = dict(heads=2, nope_dim=192, pe_dim=64, v_dim=256, kv_rank=32, eps=1e-5)
LENGTH, HIDDEN = 256, 48


def _cut_mixer(params, x, *, heads, nope_dim, pe_dim, v_dim, kv_rank, eps, q_rank=None,
               rope_theta=None):
    """The parent's ``MLAMixer``: every projection's output cut into ``[B,
    T, H, D]``, the rotary parts de-interleaved by a strided slice and a
    concatenate, the core given the shared key part beside the heads."""
    batch, length, _ = x.shape

    def norm(a, weight):
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps) * weight

    if q_rank is None:
        q = x @ params["q_proj"]["kernel"]
    else:
        q = norm(x @ params["q_a_proj"]["kernel"], params["q_a_norm"]["weight"]
                 ) @ params["q_b_proj"]["kernel"]
    q = q.reshape(batch, length, heads, nope_dim + pe_dim)
    latent = x @ params["kv_a_proj"]["kernel"]
    k_pe = latent[..., kv_rank:]
    kv = (norm(latent[..., :kv_rank], params["kv_a_norm"]["weight"])
          @ params["kv_b_proj"]["kernel"]).reshape(batch, length, heads, nope_dim + v_dim)
    q_pe = q[..., nope_dim:]
    if rope_theta is not None:
        q_pe, k_pe = rotate_by_position(q_pe, rope_theta), rotate_by_position(k_pe, rope_theta)
    out = blocked_causal_attention(
        q[..., :nope_dim], kv[..., :nope_dim], kv[..., nope_dim:], q_shared=q_pe,
        k_shared=k_pe, scale=(nope_dim + pe_dim) ** -0.5)
    return out.reshape(batch, length, heads * v_dim) @ params["o_proj"]["kernel"]


def _mixer_and_params(parts, sizes=GLM, length=LENGTH):
    x = jax.random.normal(jax.random.PRNGKey(4), (1, length, HIDDEN))
    mixer = MLAMixer(**sizes, **parts)
    params = mixer.init(jax.random.PRNGKey(5), x)["params"]
    # weights far from their initial values (scores away from uniform), the norms' too
    params = jax.tree.map(lambda a: (8.0 if a.ndim == 2 else 1.0) * a + 0.3 * jax.random.normal(
        jax.random.PRNGKey(a.size), a.shape) * jnp.abs(a).mean(), params)
    return mixer, params, x


def _value_and_grads(fn, params, x):
    cotangent = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    return jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(cotangent * fn(p, x)), (0, 1)))(params, x)


def _rise(before: dict, prefix: str) -> dict:
    after = telemetry.registry().counters_snapshot()
    return {key: value - before.get(key, 0.0) for key, value in after.items()
            if key.startswith(prefix) and value != before.get(key, 0.0)}


PARTS = [dict(q_rank=24, rope_theta=1e4), dict(q_rank=24), dict(rope_theta=1e4), dict()]
IDS = ["low_rank_rotary", "low_rank_no_rotary", "whole_rotary", "whole_no_rotary"]


@pytest.mark.parametrize("parts", PARTS, ids=IDS)
def test_the_mixer_on_the_rows_is_the_cut_mixer(parts):
    """Value, the input's gradient and every parameter's, float32 products
    on both sides: the two agree to float32 rounding."""
    mixer, params, x = _mixer_and_params(parts)
    before = telemetry.registry().counters_snapshot()
    with jax.default_matmul_precision("highest"):
        ours, ours_grads = _value_and_grads(
            lambda p, x: mixer.apply({"params": p}, x), params, x)
        assert _rise(before, "faa_attention_operands_traced_total") == {
            'faa_attention_operands_traced_total{form="rows",mixer="mla"}': 1.0}
        theirs, their_grads = _value_and_grads(
            lambda p, x: _cut_mixer(p, x, **GLM, **parts), params, x)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-5, abs=1e-4)
    assert jax.tree.structure(ours_grads) == jax.tree.structure(their_grads)
    for mine, cut in zip(jax.tree.leaves(ours_grads), jax.tree.leaves(their_grads)):
        assert mine.shape == cut.shape
        assert np.abs(np.asarray(mine) - np.asarray(cut)).max() <= 1e-5 * max(
            np.abs(np.asarray(cut)).max(), 1.0)


def test_at_the_chips_own_precision_both_round_the_same_operands():
    """bfloat16 operands into the kernels' products (what the chip's default
    precision is; asked for here, where the default is float32): the row
    pass rounds what the cut path's ``convert`` rounds, the rotary lanes in
    another order."""
    parts = dict(q_rank=24, rope_theta=1e4)
    mixer, params, x = _mixer_and_params(parts)
    rounded = lambda fn: lambda p, x: _with_bfloat16_operands(fn, p, x)
    ours, ours_grads = _value_and_grads(rounded(lambda p, x: mixer.apply({"params": p}, x)),
                                        params, x)
    theirs, their_grads = _value_and_grads(
        rounded(lambda p, x: _cut_mixer(p, x, **GLM, **parts)), params, x)
    assert float(ours) == pytest.approx(float(theirs), rel=2e-2, abs=2e-2)
    for mine, cut in zip(jax.tree.leaves(ours_grads), jax.tree.leaves(their_grads)):
        assert np.abs(np.asarray(mine) - np.asarray(cut)).max() <= 3e-2 * np.abs(
            np.asarray(cut)).max()


def _with_bfloat16_operands(fn, params, x):
    """`fn` with the kernels' products taking bfloat16 operands, as on the
    chip, and every other product float32."""
    exact = attention.kda._float32_products
    attention.kda._float32_products = lambda: False
    try:
        with jax.default_matmul_precision("highest"):
            return fn(params, x)
    finally:
        attention.kda._float32_products = exact


def test_the_parameter_tree_is_the_parents():
    """Names, shapes and initial values: ``kv_b_proj``'s kernel is the one
    ``nn.Dense`` made under that name (``benchmarks/flops``, the references
    and checkpoints read this tree)."""
    x = jnp.zeros((1, LENGTH, HIDDEN))

    class Parent(nn.Module):
        @nn.compact
        def __call__(self, x):
            dense = token_blocks.dense
            q = dense(2 * 256, "q_b_proj", jnp.float32)(token_blocks.RMSNorm(1e-5, name="q_a_norm")(
                dense(24, "q_a_proj", jnp.float32)(x)))
            latent = dense(32 + 64, "kv_a_proj", jnp.float32)(x)
            kv = dense(2 * (192 + 256), "kv_b_proj", jnp.float32)(
                token_blocks.RMSNorm(1e-5, name="kv_a_norm")(latent[..., :32]))
            return dense(HIDDEN, "o_proj", jnp.float32)(q + kv[..., :512])

    ours = MLAMixer(**GLM, q_rank=24, rope_theta=1e4).init(jax.random.PRNGKey(3), x)["params"]
    parents = Parent().init(jax.random.PRNGKey(3), x)["params"]
    assert jax.tree.structure(ours) == jax.tree.structure(parents)
    for mine, theirs in zip(jax.tree.leaves(ours), jax.tree.leaves(parents)):
        assert mine.dtype == theirs.dtype and np.array_equal(np.asarray(mine), np.asarray(theirs))
    assert {name: tuple(leaf["kernel"].shape) for name, leaf in ours.items()
            if "kernel" in leaf} == {
        "q_a_proj": (HIDDEN, 24), "q_b_proj": (24, 512), "kv_a_proj": (HIDDEN, 96),
        "kv_b_proj": (32, 896), "o_proj": (512, HIDDEN)}


def _calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, in order, by kernel name."""
    found = []

    def walk(inner):
        for eqn in inner.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"], eqn))
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


def test_no_array_of_a_projections_size_is_moved_between_the_projections_and_o_proj():
    """In the jaxpr of the mixer's value-and-gradient at GLM's head widths no
    ``transpose``, ``concatenate``, ``pad``, ``broadcast_in_dim``, ``gather``
    or strided ``slice`` writes an array the size of a projection's output
    (the weights' arrangement, the ``[B, T, 128]`` shared part and the
    products' own operand transposes are smaller, or none of these); the
    kernels' operands are the rows, rounded by the row pass; and the cut
    mixer's jaxpr, read the same way, is full of them."""
    parts = dict(q_rank=24, rope_theta=1e4)
    mixer, params, x = _mixer_and_params(parts)
    rows_size = x.shape[1] * GLM["heads"] * 256

    def moved(fn):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(
            lambda p, x: jnp.sum(fn(p, x) ** 2), (0, 1)))(params, x)
        found = []

        def walk(inner):
            for eqn in inner.eqns:
                name = eqn.primitive.name
                if name == "pallas_call":
                    continue
                strided = name == "slice" and any(
                    s != 1 for s in (eqn.params.get("strides") or ()))
                weights = name == "transpose" and eqn.outvars[0].aval.ndim == 2 and \
                    x.shape[1] not in eqn.outvars[0].aval.shape
                if (name in ("broadcast_in_dim", "concatenate", "pad", "transpose", "gather")
                        or strided) and not weights:
                    found.extend((name, var.aval.shape) for var in eqn.outvars
                                 if np.prod(var.aval.shape) >= rows_size)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        return found, jaxpr

    # the test's own ``** 2``: its cotangent is the output's size, no projection's
    found, jaxpr = moved(lambda p, x: mixer.apply({"params": p}, x))
    assert not found, found
    names = [name for name, _ in _calls(jaxpr)]
    assert sorted(names) == ["mla_attention_backward", "mla_attention_forward",
                             "mla_rows_lay", "mla_rows_turn", "mla_rows_unlay"]
    by_name = dict(_calls(jaxpr))
    assert [(v.aval.shape, str(v.aval.dtype)) for v in by_name["mla_attention_forward"].invars] \
        == [((1, LENGTH, 512), "float32")] * 3                  # the CPU's products are float32
    assert [v.aval.shape for v in by_name["mla_attention_backward"].outvars] == [
        (1, LENGTH, 512)] * 3
    cut, _ = moved(lambda p, x: _cut_mixer(p, x, **GLM, **parts))
    assert {name for name, _ in cut} >= {"concatenate", "pad"}


@pytest.mark.parametrize("rotary", [True, False], ids=["rotary", "no_rotary"])
def test_the_row_pass_is_its_arithmetic_in_jnp_and_unlay_its_transpose(rotary):
    batch, length, heads, dim, shared = 2, 64, 3, 256, 64
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(key, (batch, length, heads * dim)) for key in keys[:2])
    part = jax.random.normal(keys[2], (batch, length, shared))
    lay = (shared, 1e4 if rotary else None)
    angle = attention._angle(lay, length)

    def plain(q, k, part):
        laid = attention._laid_xla(q.reshape(batch, length, heads, dim),
                                   k.reshape(batch, length, heads, dim), part, lay[1])
        # `k`'s own lanes under the shared part count: the pass adds
        return (laid[0].reshape(q.shape),
                laid[1].reshape(k.shape) + k * (jnp.arange(heads * dim) % dim >= dim - shared))

    ours = (mlarows.turn(q, heads, angle, exact=True, interpret=True),
            mlarows.lay(k, heads, part, angle, exact=True, interpret=True))
    theirs, transpose = jax.vjp(plain, q, k, part)
    for mine, hand in zip(ours, theirs):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(hand), rtol=0, atol=2e-6)
    for rounded in (mlarows.turn(q, heads, angle, interpret=True),
                    mlarows.lay(k, heads, part, angle, interpret=True)):
        assert rounded.dtype == jnp.bfloat16
    dq, dk = (jax.random.normal(key, q.shape) for key in keys[2:])
    d_q, d_k, d_part = transpose((dq, dk))
    mine_q, mine_part = mlarows.unlay(dq, dk, heads, shared, angle, interpret=True)
    np.testing.assert_allclose(np.asarray(mine_q), np.asarray(d_q), rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(mine_part), np.asarray(d_part), rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(dk))    # as it lies


@pytest.mark.parametrize("sizes, form", [
    (dict(GLM), "rows"),
    (dict(GLM, nope_dim=128, pe_dim=64, v_dim=128), "cut"),         # Kimi Linear's: 192 a head
    (dict(GLM, nope_dim=192, pe_dim=64, v_dim=128), "rows"),
    (dict(GLM, nope_dim=128, pe_dim=128, v_dim=128), "rows"),
    (dict(GLM, nope_dim=256, pe_dim=256, v_dim=128), "cut"),        # the part no one piece
], ids=["glm", "kimi", "values_of_128", "a_piece_of_its_own", "two_pieces"])
def test_the_shapes_alone_choose_the_path(sizes, form):
    """Whole lanes a head (key and values) and the shared part inside one
    piece: rows; anything else, Kimi Linear's 128 + 64 among it, the cut
    path it had — pinned here (CHANGES.md, PR 52)."""
    mixer, params, x = _mixer_and_params(dict(rope_theta=1e4), sizes)
    before = telemetry.registry().counters_snapshot()
    jax.eval_shape(lambda p, x: mixer.apply({"params": p}, x), params, x)
    assert _rise(before, "faa_attention_operands_traced_total") == {
        f'faa_attention_operands_traced_total{{form="{form}",mixer="mla"}}': 1.0}


#: both latent-attention families cut to a tiny program but for their
#: published head widths: conf, the cut, layers held, cores, how they come
TINY = {
    "glm4_moe_lite": ("glm47_flash", dict(
        hidden_size=32, intermediate_size=48, kv_lora_rank=8, q_lora_rank=12,
        moe_intermediate_size=16, num_attention_heads=2, n_routed_experts=8,
        num_experts_per_tok=2, vocab_size=64), 2, 3, "rows"),
    "kimi_linear": ("kimi_linear_48b_a3b", dict(
        hidden_size=32, intermediate_size=48, kv_lora_rank=8, moe_intermediate_size=16,
        num_attention_heads=2, num_experts=16, num_experts_per_token=4, vocab_size=64),
        4, 1, "cut"),
}


def _tiny_program(kind):
    """``(apply(params), params' shapes)``: every head's logits of the cut
    model on a sequence of two tiles."""
    conf, cut, layers, _, _ = TINY[kind]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "confs", conf + ".yaml")) as fh:
        whole = yaml.safe_load(fh)
    whole["model"].update(cut)
    if "linear_attn_config" in whole["model"]:
        whole["model"]["linear_attn_config"].update(head_dim=8, num_heads=2)
    whole.update(layers_held=layers, experts_held=4, dataset="synthetic_tokens")
    module = get_model(model_conf_of(whole), 64)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, LENGTH), 0, 64)
    method, args = ("logits_and_mtp_logits", (ids, ids)) if hasattr(
        module, "logits_and_mtp_logits") else (None, (ids,))

    def apply(params):
        return module.apply({"params": params}, *args, method=method,
                            mutable=[token_blocks.STEP_STATS])[0]

    return apply, lambda: jax.jit(module.init)({"params": jax.random.PRNGKey(0)}, ids)["params"]


@pytest.mark.parametrize("kind", sorted(TINY))
def test_a_programs_cores_are_counted_by_how_their_operands_came(kind):
    """GLM-4.7-Flash's two layers and the module's block (192 + 64 on 256)
    count three ``{mla, rows}``, Kimi Linear's one latent layer among four
    (128 + 64 on 128) one ``{mla, cut}``, and nothing else."""
    apply, init = _tiny_program(kind)
    params = jax.eval_shape(init)
    before = telemetry.registry().counters_snapshot()
    jax.eval_shape(apply, params)
    cores, form = TINY[kind][3:]
    assert _rise(before, "faa_attention_operands_traced_total") == {
        f'faa_attention_operands_traced_total{{form="{form}",mixer="mla"}}': float(cores)}


def test_a_glm_program_on_the_rows_is_the_program_cut_into_heads(monkeypatch):
    """Both heads' logits of the tiny GLM-4.7-Flash, float32 products: the
    rows path against the same parameters with the shape rule made to say no
    (the parent's path), to float32 rounding."""
    apply, init = _tiny_program("glm4_moe_lite")
    params = init()
    with jax.default_matmul_precision("highest"):
        ours = jax.jit(apply)(params)
        monkeypatch.setattr(mlarows, "admits", lambda dim, shared: False)
        before = telemetry.registry().counters_snapshot()
        theirs = jax.jit(lambda p: apply(p))(params)
    assert _rise(before, "faa_attention_operands_traced_total") == {
        'faa_attention_operands_traced_total{form="cut",mixer="mla"}': 3.0}
    for mine, cut in zip(ours, theirs):
        assert np.abs(np.asarray(mine) - np.asarray(cut)).max() <= 1e-5 * np.abs(
            np.asarray(cut)).max()


def test_a_sequence_the_kernels_do_not_take_is_the_cut_mixer_too():
    """One tile's tokens: the entry lays the rows in jnp and takes the XLA
    form; no fused core, so no count of operands."""
    parts = dict(q_rank=24, rope_theta=1e4)
    mixer, params, x = _mixer_and_params(parts, length=96)
    before = telemetry.registry().counters_snapshot()
    with jax.default_matmul_precision("highest"):
        ours, ours_grads = _value_and_grads(
            lambda p, x: mixer.apply({"params": p}, x), params, x)
        theirs, their_grads = _value_and_grads(
            lambda p, x: _cut_mixer(p, x, **GLM, **parts), params, x)
    assert not _rise(before, "faa_attention_operands_traced_total")
    assert float(ours) == pytest.approx(float(theirs), rel=1e-5, abs=1e-4)
    for mine, cut in zip(jax.tree.leaves(ours_grads), jax.tree.leaves(their_grads)):
        assert np.abs(np.asarray(mine) - np.asarray(cut)).max() <= 1e-5 * max(
            np.abs(np.asarray(cut)).max(), 1.0)


def test_what_the_entry_refuses():
    q = jnp.zeros((1, 256, 512))
    with pytest.raises(ValueError, match="carry no shared key part"):
        blocked_causal_attention(q, q, q, scale=1.0, heads=2, q_shared=jnp.zeros((1, 256, 2, 64)),
                                 k_shared=jnp.zeros((1, 256, 64)))
    with pytest.raises(ValueError, match="inside the heads' lanes"):
        blocked_causal_attention(q, q, q, scale=1.0, heads=2, theta=1e4)
    with pytest.raises(ValueError, match="inside the heads' lanes"):
        blocked_causal_attention(*(q.reshape(1, 256, 2, 256),) * 3, scale=1.0, theta=1e4)


def test_the_keys_matrix_has_zero_columns_where_the_shared_part_goes():
    kernel = jnp.arange(3 * 2 * 10, dtype=jnp.float32).reshape(3, 20) + 1.0
    keys, values = key_value_columns(kernel, heads=2, nope=4, pe=2)
    by_head = np.asarray(kernel).reshape(3, 2, 10)
    assert keys.shape == (3, 12) and values.shape == (3, 12)
    np.testing.assert_array_equal(np.asarray(keys).reshape(3, 2, 6)[..., :4], by_head[..., :4])
    assert not np.asarray(keys).reshape(3, 2, 6)[..., 4:].any()
    np.testing.assert_array_equal(np.asarray(values).reshape(3, 2, 6), by_head[..., 4:])


# --------------------------------------------- the paths this leaves alone

@pytest.mark.parametrize("heads, kv_heads, dim", [(4, 1, 128), (4, 2, 64)],
                         ids=["heads_of_128", "heads_of_64"])
def test_the_gqa_mixers_never_meet_the_row_pass(monkeypatch, heads, kv_heads, dim):
    """``GQAMixer`` at heads of 128 (rows) and of 64 (cut), no key span: with
    everything the latent rows brought made to raise — the row pass, the
    weights' cut, the shared part's arithmetic in jnp — value and gradient
    lower to the text they lower to with it, character for character, and
    count ``gqa`` operands alone."""
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 256, 96))
    mixer = GQAMixer(heads, kv_heads, dim, qk_norm_eps=1e-5, rope_theta=1e4)
    params = mixer.init(jax.random.PRNGKey(0), x)["params"]

    def lowered():
        jax.clear_caches()
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(mixer.apply({"params": p}, x) ** 2), (0, 1))).lower(
                params, x).as_text()

    before = telemetry.registry().counters_snapshot()
    text = lowered()
    form = "rows" if dim == 128 else "cut"
    assert _rise(before, "faa_attention_operands_traced_total") == {
        f'faa_attention_operands_traced_total{{form="{form}",mixer="gqa"}}': 1.0}

    def never(*args, **kwargs):
        raise AssertionError("a grouped-query mixer met the latent rows")

    with monkeypatch.context() as patch:
        for name in ("turn", "lay", "unlay", "admits"):
            patch.setattr(mlarows, name, never)
        patch.setattr(attention, "_laid_xla", never)
        patch.setattr(attention, "_angle", never)
        patch.setattr(token_blocks, "proj_columns", never)
        patch.setattr(token_blocks, "key_value_columns", never)
        assert lowered() == text
