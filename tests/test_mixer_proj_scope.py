"""One scope over the mixers' projections (``core/scopes.py::MIXER_PROJ``,
entered by ``models/token_blocks.py::proj``).  For a tiny model of each of
the five token families the lowered train step holds ``faa_mixer_proj`` only
nested in a mixer's scope and outside its core's, on every product the
family's mixers make and on no other ``*_proj`` of the model (the MLPs, the
shared experts, ``mtp_eh_proj`` stay outside it); and the scope is metadata
alone: the lowered text without locations is the same text with the scope
entered and with it patched to a null context, and so are the parameters'
names and shapes."""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
import yaml

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.models import get_model, model_conf_of
from fast_autoaugment_tpu.ops.optim import build_optimizer
from fast_autoaugment_tpu.train.steps import create_train_state, make_token_step_body

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IDS, LENGTH = 64, 16

MIXERS = {scopes.MLA, scopes.GQA, scopes.SHORT_CONV, scopes.KDA, scopes.MAMBA2}
CORES = {scopes.MLA_ATTENTION, scopes.GQA_ATTENTION, scopes.SHORT_CONV_GATE,
         scopes.KDA_SCAN, scopes.SSD_SCAN}

#: family: (its shipped conf, the cut of its ``model`` block, layers held, the
#: mixer scopes its step holds, the products under ``faa_mixer_proj``)
FAMILIES = {
    "kimi_linear": ("kimi_linear_48b_a3b", dict(
        hidden_size=32, intermediate_size=48, kv_lora_rank=8, moe_intermediate_size=16,
        num_attention_heads=2, num_experts=16, num_experts_per_token=4,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, vocab_size=IDS),
        4, {scopes.KDA, scopes.MLA},
        {"q_proj", "k_proj", "v_proj", "f_a_proj", "f_b_proj", "b_proj", "g_a_proj",
         "g_b_proj", "o_proj", "kv_a_proj", "kv_b_proj"}),
    "glm4_moe_lite": ("glm47_flash", dict(
        hidden_size=32, intermediate_size=48, kv_lora_rank=8, q_lora_rank=12,
        moe_intermediate_size=16, num_attention_heads=2, n_routed_experts=8,
        num_experts_per_tok=2, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        vocab_size=IDS),
        2, {scopes.MLA},
        {"q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj"}),
    "nemotron_h": ("nemotron3_nano_30b_a3b", dict(
        hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
        ssm_state_size=16, chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, n_routed_experts=16, num_experts_per_tok=2, moe_intermediate_size=16,
        moe_shared_expert_intermediate_size=24, vocab_size=IDS,
        hybrid_override_pattern="M*E", num_hidden_layers=3),
        3, {scopes.MAMBA2, scopes.GQA},
        {"in_proj", "out_proj", "q_proj", "k_proj", "v_proj", "o_proj"}),
    "afmoe": ("trinity_mini", dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        intermediate_size=48, moe_intermediate_size=16, num_experts=16,
        num_experts_per_tok=2, vocab_size=IDS, sliding_window=8, num_hidden_layers=3,
        layer_types=["sliding_attention", "full_attention", "sliding_attention"],
        num_dense_layers=2),
        3, {scopes.GQA},
        {"q_proj", "k_proj", "v_proj", "gate_proj", "o_proj"}),
    "lfm2_moe": ("lfm2_8b_a1b", dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, moe_intermediate_size=16, num_experts=16,
        num_experts_per_tok=2, vocab_size=IDS, num_hidden_layers=3,
        layer_types=["conv", "full_attention", "conv"]),
        3, {scopes.SHORT_CONV, scopes.GQA},
        {"in_proj", "out_proj", "q_proj", "k_proj", "v_proj", "o_proj"}),
}


def _products(path: str):
    """``(name, what precedes it)`` of every ``*_proj`` component of a path
    (a flax module's name; the scope's own name is none)."""
    parts = path.split("/")
    for at, part in enumerate(parts):
        if part.endswith("_proj") and part != scopes.MIXER_PROJ:
            yield part, parts[:at]


def _model(family: str):
    name, cut, held, _, _ = FAMILIES[family]
    with open(os.path.join(REPO, "confs", name + ".yaml")) as fh:
        conf = yaml.safe_load(fh)
    conf["model"].update(cut)
    if family == "kimi_linear":
        conf["model"]["linear_attn_config"].update(head_dim=8, num_heads=2)
    conf.update(layers_held=held, experts_held=4, dataset="synthetic_tokens")
    return conf, get_model(model_conf_of(conf), IDS)


def _lowered_step(family: str):
    conf, model = _model(family)
    optimizer = build_optimizer(conf["optimizer"], lambda step: 1e-3)
    ids = jnp.zeros((2, LENGTH + 1), jnp.int32)
    state = jax.eval_shape(lambda: create_train_state(
        model, optimizer, jax.random.PRNGKey(0), ids[:, :-1], use_ema=False))
    lowered = jax.jit(make_token_step_body(model, optimizer)).lower(
        state, ids, jnp.zeros(2, jnp.int32), None, None)
    return lowered, state


@contextlib.contextmanager
def _no_mixer_proj_scope(monkeypatch):
    """``jax.named_scope(MIXER_PROJ)`` a null context, every other scope itself."""
    real = jax.named_scope
    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope", lambda name: (
            contextlib.nullcontext() if name == scopes.MIXER_PROJ else real(name)))
        yield


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def lowered(request):
    step, state = _lowered_step(request.param)
    names = set(re.findall(r'loc\("([^"]*)"(?!:)', step.as_text(debug_info=True)))
    return request.param, step, state, names


def test_the_scope_is_nested_in_a_mixer_and_outside_its_core(lowered):
    family, _, _, names = lowered
    _, _, _, mixers, _ = FAMILIES[family]
    under = [name for name in names if scopes.MIXER_PROJ in scopes.scope_of(name)]
    assert under
    found = set()
    for name in under:
        chain = scopes.scope_of(name)
        before = chain[:chain.index(scopes.MIXER_PROJ)]
        assert chain[0] == scopes.MODEL and MIXERS.intersection(before), name
        assert not CORES.intersection(chain), name
        assert chain[-1] == scopes.MIXER_PROJ, name     # nothing nests inside it
        found |= MIXERS.intersection(before)
    assert found == mixers
    # all three passes hold it: the products run forward, again under nn.remat, backward
    assert {scopes.pass_of(name) for name in under} == set(scopes.PASSES)


def test_every_product_of_a_mixer_and_no_other(lowered):
    family, _, _, names = lowered
    products = FAMILIES[family][4]
    seen, outside = set(), set()
    for name in names:
        path = scopes._scoped_path(name) or name
        for product, before in _products(path):
            if before[-1] == scopes.MIXER_PROJ:
                seen.add(product)
            else:
                # a mixer's own product outside the scope: there is none
                assert not MIXERS.intersection(scopes.scope_of("/".join(before))), name
                outside.add(product)
    assert seen == products
    # the scope opens on a product and on nothing else: what follows it is a *_proj
    for name in names:
        path = scopes._scoped_path(name)
        if scopes.MIXER_PROJ + "/" in path:
            after = path.split(scopes.MIXER_PROJ + "/", 1)[1].split("/", 1)[0]
            assert after in products, name
    # the MLPs', the shared experts' and the MTP module's products stay outside
    assert outside & {"up_proj", "down_proj"}
    if family == "glm4_moe_lite":
        assert "mtp_eh_proj" in outside


def test_the_scope_is_metadata_alone(lowered, monkeypatch):
    family, step, state, _ = lowered
    with _no_mixer_proj_scope(monkeypatch):
        bare, bare_state = _lowered_step(family)
        located = bare.as_text(debug_info=True)
    assert scopes.MIXER_PROJ not in located
    assert scopes.MIXER_PROJ in step.as_text(debug_info=True)
    assert bare.as_text() == step.as_text()
    assert jax.tree.structure(bare_state) == jax.tree.structure(state)
    assert jax.tree.leaves(bare_state) == jax.tree.leaves(state)


def test_proj_builds_denses_own_layer():
    """The same parameter, name and initialiser as ``dense``'s, so checkpoints
    and references do not move; the initialiser is an argument (Mamba-2's
    ``out_proj``)."""
    from flax import linen as nn

    from fast_autoaugment_tpu.models.token_blocks import dense, proj

    class Both(nn.Module):
        scoped: bool

        @nn.compact
        def __call__(self, x):
            if self.scoped:
                return proj(x, 6, "q_proj", jnp.float32)
            return dense(6, "q_proj", jnp.float32)(x)

    x = jnp.ones((2, 3, 4))
    key = jax.random.PRNGKey(5)
    scoped, plain = (Both(s).init(key, x) for s in (True, False))
    assert jax.tree.structure(scoped) == jax.tree.structure(plain)
    assert (scoped["params"]["q_proj"]["kernel"] == plain["params"]["q_proj"]["kernel"]).all()
    assert set(scoped["params"]["q_proj"]) == {"kernel"}

    class Small(nn.Module):
        @nn.compact
        def __call__(self, x):
            return proj(x, 6, "out_proj", jnp.float32, kernel_init=nn.initializers.zeros)

    assert not Small().init(key, x)["params"]["out_proj"]["kernel"].any()
