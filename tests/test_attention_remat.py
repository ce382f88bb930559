"""What the token blocks' ``nn.remat`` keeps of an attention core
(``models/token_blocks.py::remat_block`` over the names ``ops/attention.py``'s
forward rule gives its two products): the four token models at a small
shape that takes the *fused* form — a head of 128, a sequence of two tiles
of 128, the kernels interpreted; the usual test heads of 8 / 5 / 4 take the
XLA form, which names nothing — against the same model under a bare
``nn.remat``, the parent's.  The loss's gradient runs the forward kernel
once a core (twice under the bare one) and is the bare one's bit for bit;
a model with ``remat: false`` and a forward-only program lower to the text
they lowered to before there were names or a policy; the two trace-time
counters read the cores offered to the policy and their bytes, by span."""

import contextlib
import functools
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from flax import linen as nn

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.models import get_model, model_conf_of
from fast_autoaugment_tpu.models.token_blocks import STEP_STATS
from fast_autoaugment_tpu.ops import attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENGTH, HEAD, IDS = 256, 128, 64

#: a model type (its family module's name): its shipped conf and, of the
#: cut below (every width but the attention head's cut, the structure
#: kept), the query heads a core has and the cores held by key span
KINDS = {
    "kimi_linear": ("kimi_linear_48b_a3b", 1, {"none": 1}),
    "glm4_moe_lite": ("glm47_flash", 1, {"none": 3}),
    "nemotron_h": ("nemotron3_nano_30b_a3b", 2, {"none": 1}),
    "afmoe": ("trinity_mini", 2, {"128": 3, "none": 1}),
}


def _conf(kind: str, **model) -> dict:
    with open(os.path.join(REPO, "confs", KINDS[kind][0] + ".yaml")) as fh:
        conf = yaml.safe_load(fh)
    cut = conf["model"]
    if kind == "kimi_linear":       # three KDA layers and the one latent core
        cut.update(hidden_size=32, intermediate_size=48, kv_lora_rank=8,
                   moe_intermediate_size=16, num_attention_heads=1, num_experts=16,
                   num_experts_per_token=4, qk_nope_head_dim=HEAD, qk_rope_head_dim=4,
                   v_head_dim=HEAD, vocab_size=IDS)
        cut["linear_attn_config"].update(head_dim=8, num_heads=2)
        conf.update(layers_held=4)
    elif kind == "glm4_moe_lite":   # a dense layer, an expert layer, the MTP module
        cut.update(hidden_size=32, intermediate_size=48, kv_lora_rank=8, q_lora_rank=12,
                   moe_intermediate_size=16, num_attention_heads=1, n_routed_experts=8,
                   num_experts_per_tok=2, qk_nope_head_dim=HEAD, qk_rope_head_dim=4,
                   v_head_dim=HEAD, vocab_size=IDS)
        conf.update(layers_held=2)
    elif kind == "nemotron_h":      # a Mamba-2 layer, the attention layer, an expert layer
        cut.update(hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
                   ssm_state_size=16, chunk_size=8, num_attention_heads=2,
                   num_key_value_heads=1, head_dim=HEAD, n_routed_experts=16,
                   num_experts_per_tok=2, moe_intermediate_size=16,
                   moe_shared_expert_intermediate_size=24, vocab_size=IDS,
                   hybrid_override_pattern="M*E", num_hidden_layers=3)
        conf.update(layers_held=3)
    else:                           # three window layers and the full one
        # no multiplier on the embedding: XLA's CPU backend fuses it into
        # whatever reads the first block's input, and a sum's last bit then
        # follows the program round it (a bare ``nn.remat`` and ``remat:
        # false`` differ by it too) — nothing the cores' names decide
        cut.update(hidden_size=32, num_attention_heads=2, num_key_value_heads=1,
                   head_dim=HEAD, intermediate_size=48, moe_intermediate_size=16,
                   num_experts=16, num_experts_per_tok=2, vocab_size=IDS,
                   sliding_window=128, num_hidden_layers=4, mup_enabled=False,
                   layer_types=cut["layer_types"][:4])
        conf.update(layers_held=4)
    cut.update(model)
    conf.update(experts_held=4, dataset="synthetic_tokens")
    return conf


@functools.cache
def _inputs_and_params(kind: str):
    """Seeded ids and the cut model's seeded parameters: the same under
    either wrapper, with ``remat`` and without."""
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, LENGTH + 1), 0, IDS)
    module = get_model(model_conf_of(_conf(kind)), IDS)
    params = jax.jit(module.init)({"params": jax.random.PRNGKey(3)}, ids[:, :-1])["params"]
    return ids[:, :-1], ids[:, 1:], params


def _loss_of(kind: str, **model):
    """``(loss(params), logits(params), params)`` of the cut model: the
    step body's loss (``train/steps.py::make_token_step_body``)."""
    module = get_model(model_conf_of(_conf(kind, **model)), IDS)
    inputs, targets, params = _inputs_and_params(kind)

    def loss(params):
        if not hasattr(module, "loss_terms"):
            logits, _ = module.apply({"params": params}, inputs, mutable=[STEP_STATS])
            return -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                        targets[..., None], -1).mean()
        (nll, _, further), _ = module.apply(
            {"params": params}, inputs, targets, mutable=[STEP_STATS], method="loss_terms")
        return nll.mean() + sum(weight * value.mean()
                                for value, weight in further.values() if weight)

    def logits(params):
        return module.apply({"params": params}, inputs, mutable=[STEP_STATS])[0]

    return loss, logits, params


@contextlib.contextmanager
def _parent(kind: str, monkeypatch):
    """The tree before the names and the policy: a bare ``nn.remat`` where
    the family wraps its block, no name on the forward rule's products."""
    family = importlib.import_module("fast_autoaugment_tpu.models." + kind)
    with monkeypatch.context() as patch:
        patch.setattr(family, "remat_block", nn.remat)
        patch.setattr(attention, "checkpoint_name", lambda value, name: value)
        yield


def _kernels(jaxpr, counts=None) -> dict:
    """``{name: calls}`` of the ``pallas_call``s of a jaxpr, those of the
    jaxprs its equations hold among them."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _kernels(inner, counts)
    return counts


def _rise(before: dict, prefix: str) -> dict:
    after = telemetry.registry().counters_snapshot()
    return {key: value - before.get(key, 0.0) for key, value in after.items()
            if key.startswith(prefix) and value != before.get(key, 0.0)}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_forward_kernel_runs_once_a_core_and_the_gradient_is_the_bare_remats(
        kind, monkeypatch):
    _, heads, by_span = KINDS[kind]
    cores = sum(by_span.values())
    loss, _, params = _loss_of(kind)
    before = telemetry.registry().counters_snapshot()
    kept = _kernels(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert kept["mla_attention_forward"] == kept["mla_attention_backward"] == cores
    # trace time: every core was fused and offered to the policy, by span,
    # with the bytes of its float32 output and its rows' log-sum-exp
    a_core = 4 * LENGTH * heads * HEAD + 4 * heads * LENGTH
    assert _rise(before, "faa_attention_outputs_named_total") == {
        f'faa_attention_outputs_named_total{{span="{span}"}}': float(count)
        for span, count in by_span.items()}
    assert _rise(before, "faa_attention_kept_bytes_total") == {
        f'faa_attention_kept_bytes_total{{span="{span}"}}': float(count * a_core)
        for span, count in by_span.items()}
    assert _rise(before, "faa_attention_cores_traced_total") == {
        f'faa_attention_cores_traced_total{{form="fused",span="{span}"}}': float(count)
        for span, count in by_span.items()}
    ours = jax.jit(jax.grad(loss))(params)
    with _parent(kind, monkeypatch):
        bare_loss, _, _ = _loss_of(kind)
        bare = _kernels(jax.make_jaxpr(jax.grad(bare_loss))(params).jaxpr)
        theirs = jax.jit(jax.grad(bare_loss))(params)
    # the block computed again ran the kernel again, to rebuild the residuals
    assert bare["mla_attention_forward"] == 2 * cores
    assert bare["mla_attention_backward"] == cores
    leaves, their_leaves = jax.tree.leaves(ours), jax.tree.leaves(theirs)
    assert len(leaves) == len(their_leaves) > 0
    assert any(np.any(np.asarray(leaf)) for leaf in leaves)
    for (path, leaf), other in zip(jax.tree_util.tree_leaves_with_path(ours), their_leaves):
        assert np.array_equal(np.asarray(leaf), np.asarray(other)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("program", ["remat_false_gradient", "forward_only"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_without_remat_and_forward_only_the_lowered_text_is_the_parents(
        kind, program, monkeypatch):
    """A name is the identity in the lowered program and a policy is read
    by a backward pass alone: a forward-only program (``only_eval``, the
    comparison's passes) lowers to the parent's text, character for
    character, and a model with ``remat: false`` (no ``nn.remat`` at all)
    to the parent's text but for the serial numbers MLIR's symbol table
    gives private functions of one name (``@_where_217``: a count of the
    renamings before it, which the names' equations in the forward rule
    move by one).  Against the tree before PR 47 itself, the same: PR 47's
    scratch comparison, CHANGES.md."""
    def text():
        if program == "forward_only":
            _, logits, params = _loss_of(kind)
            return jax.jit(logits).lower(params).as_text()
        loss, _, params = _loss_of(kind, remat=False)
        return re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      jax.jit(jax.grad(loss)).lower(params).as_text())

    before = telemetry.registry().counters_snapshot()
    ours = text()
    named = _rise(before, "faa_attention_outputs_named_total")
    assert all('form="fused"' in key
               for key in _rise(before, "faa_attention_cores_traced_total"))
    # a forward-only program runs no forward rule: nothing is named there
    assert bool(named) == (program == "remat_false_gradient")
    with _parent(kind, monkeypatch):
        theirs = text()
    assert ours == theirs


def test_the_xla_form_names_nothing():
    """The usual test heads take ``_blocked_xla``, whose block body stays
    under ``jax.checkpoint``: no name, no count."""
    q = k = v = jnp.ones((1, 64, 2, 8))
    before = telemetry.registry().counters_snapshot()
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        attention.blocked_causal_attention(q, k, v, scale=1.0, block=16))))(q)
    assert attention.OUT_NAME not in str(jaxpr) and attention.LSE_NAME not in str(jaxpr)
    assert not _rise(before, "faa_attention_outputs_named_total")
    assert not _rise(before, "faa_attention_kept_bytes_total")
