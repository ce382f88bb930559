"""Named scopes on the device (core/scopes.py) and the scope map at the
compile seam (core/compilecache.py::scope_map): the names are found
again in an ``op_name``, every name of the table reaches the compiled
train step, the map costs cache hits and never a miss, and a module
without scopes (the stale-cache case) is an error with a name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cc

from fast_autoaugment_tpu.core import compilecache as cc
from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.ops.augment import _AFFINE_MATRIX_FNS, OP_NAMES

# real op_name strings, as compiled.as_text() of the train step has them
OP_NAME_CASES = [
    ("plain", "jit(multi_fn)/faa_batch_gather/jit(_take)/gather",
     ("faa_batch_gather",), False),
    ("vmap", "jit(multi_fn)/vmap(faa_aug_fixed)/dynamic_slice",
     ("faa_aug_fixed",), False),
    ("op_nested_in_policy",
     "jit(multi_fn)/vmap(faa_aug_policy)/faa_aug_op_Equalize/jit(sort)/sort",
     ("faa_aug_policy", "faa_aug_op_Equalize"), False),
    ("jvp",
     "jit(multi_fn)/jvp(faa_model)/WideResNet/layer3_1/conv1/conv_general_dilated",
     ("faa_model",), False),
    ("transpose_jvp",
     "jit(multi_fn)/transpose(jvp(faa_model))/WideResNet/layer3_1/conv1/"
     "conv_general_dilated", ("faa_model",), True),
    ("transpose_jvp_loss", "jit(multi_fn)/transpose(jvp(faa_loss))/mul",
     ("faa_loss",), True),
    ("merged_instructions",
     "jit(multi_fn)/transpose(jvp(faa_loss))/mul;"
     "jit(multi_fn)/transpose(jvp(faa_loss))/broadcast_in_dim",
     ("faa_loss",), True),
    ("merged_first_unscoped",
     "jit(multi_fn)/convert_element_type;jit(multi_fn)/faa_optimizer/add",
     ("faa_optimizer",), False),
    ("no_scope", "jit(multi_fn)/jit(_threefry_split)/threefry2x32", (), False),
    ("flax_path_only", "jit(multi_fn)/jvp(WideResNet)/layer3_1/conv1/mul",
     (), False),
    ("transpose_of_flax_path_only",
     "jit(multi_fn)/transpose(jvp(WideResNet))/layer1_0/bn1/mul", (), False),
    ("empty", "", (), False),
]


@pytest.mark.parametrize("op_name,chain,backward",
                         [c[1:] for c in OP_NAME_CASES],
                         ids=[c[0] for c in OP_NAME_CASES])
def test_scope_of_and_is_backward(op_name, chain, backward):
    assert scopes.scope_of(op_name) == chain
    assert scopes.is_backward(op_name) is backward


def test_aug_op_names_carry_the_prefix():
    assert scopes.aug_op("Equalize") == "faa_aug_op_Equalize"
    for name in (scopes.BATCH_GATHER, scopes.AUG_POLICY, scopes.AUG_WARP,
                 scopes.AUG_FIXED, scopes.MODEL, scopes.LOSS, scopes.OPTIMIZER,
                 scopes.EMA, scopes.METRICS, scopes.aug_op("ShearX")):
        assert name.startswith(scopes.PREFIX)
        assert scopes.scope_of(f"jit(f)/{name}/add") == (name,)


# ------------------------------------------------------ the train step

TABLE = (scopes.BATCH_GATHER, scopes.AUG_POLICY, scopes.AUG_WARP,
         scopes.AUG_FIXED, scopes.MODEL, scopes.LOSS, scopes.OPTIMIZER,
         scopes.EMA, scopes.METRICS) + tuple(scopes.aug_op(n) for n in OP_NAMES)
# the seven whose scope holds a 2x3 matrix; their resampling is AUG_WARP's
AFFINE_OPS = {scopes.aug_op(n) for n in _AFFINE_MATRIX_FNS}


def _tiny_train_dispatch():
    """``(multi_fn, args)``: the train_dispatch program on a tiny model
    with a 2-sub-policy tensor under the exact dispatch, its state
    committed to a one-device mesh as the trainer commits it."""
    from fast_autoaugment_tpu.data.pipeline import StoredRows
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import make_mesh, replicated
    from fast_autoaugment_tpu.train.steps import (
        create_train_state,
        make_multistep_train_step,
        make_train_step_body,
    )

    mesh = make_mesh(jax.devices()[:1])
    rep = replicated(mesh)
    model = get_model({"type": "wresnet10_1"}, 10)
    opt = build_optimizer({"type": "sgd", "decay": 2e-4, "momentum": 0.9,
                           "nesterov": True}, lambda s: 0.05)
    body = make_train_step_body(model, opt, num_classes=10, cutout_length=4,
                                ema_mu=0.99, aug_dispatch="exact")
    multi = make_multistep_train_step(body, steps_per_dispatch=1)
    state = create_train_state(model, opt, jax.random.PRNGKey(0),
                               jnp.zeros((2, 8, 8, 3), jnp.float32),
                               use_ema=True)
    rng = np.random.default_rng(0)
    policy = np.array([[[7, 0.5, 0.5], [0, 0.5, 0.3]],
                       [[5, 0.9, 0.1], [14, 0.2, 0.8]]], np.float32)
    args = (jax.device_put(state, rep),
            jax.device_put(StoredRows.of(
                rng.integers(0, 256, (16, 8, 8, 3), dtype=np.uint8)), rep),
            jax.device_put(rng.integers(0, 10, (16,), np.int32), rep),
            jax.device_put(np.arange(4, dtype=np.int32)[None], rep),
            jax.device_put(policy, rep),
            jax.device_put(jax.random.PRNGKey(1), rep))
    return multi, args


@pytest.fixture(scope="module")
def called_train_dispatch(tmp_path_factory):
    """The tiny program after its first call, with a persistent cache of
    its own (process-wide state: the session's is restored after)."""
    before = jax.config.jax_compilation_cache_dir
    directory = str(tmp_path_factory.mktemp("scope_cache"))
    jax.config.update("jax_compilation_cache_dir", directory)
    jax_cc.reset_cache()
    cc._reset_stats_for_tests()
    cc.configure_compile_cache()
    multi, args = _tiny_train_dispatch()
    specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        args)
    jax.block_until_ready(multi(*args))
    yield multi, specs
    jax.config.update("jax_compilation_cache_dir", before)
    jax_cc.reset_cache()
    cc._reset_stats_for_tests()
    cc.configure_compile_cache()


def test_every_scope_of_the_table_reaches_the_compiled_step(
        called_train_dispatch):
    multi, specs = called_train_dispatch
    lowered = multi.lower(*specs)
    traced = lowered.as_text(debug_info=True)
    assert not [name for name in TABLE if name not in traced]
    text = lowered.compile().as_text()
    missing = [name for name in TABLE if name not in text]
    # Posterize and Posterize2 are one function of the value the switch
    # hands its branches, and XLA computes the pair once under one name
    posterize = {scopes.aug_op("Posterize2"), scopes.aug_op("Posterize")}
    assert len(posterize & set(missing)) <= 1
    # an affine operation's scope holds six floats of a matrix, which XLA
    # may fold into the warp that consumes them; the warp's own scope and
    # every other name come through
    assert set(missing) <= posterize | AFFINE_OPS, missing
    assert scopes.AUG_WARP not in missing
    # folded away, not left behind by a checkout from before them: no instruction
    # of theirs is in the text under a name without the scope
    assert cc.stale_scopes(traced, text) == set()
    # forward and backward of the model are told apart
    assert "jvp(faa_model)" in text
    assert "transpose(jvp(faa_model))" in text


def test_scope_map_after_first_call_is_a_cache_hit(called_train_dispatch):
    before = cc.compile_cache_stats()
    assert before["labels"]["train_dispatch"]["miss"] == 1
    modules = cc.scope_map("train_dispatch")
    assert cc.compile_cache_stats()["misses"] == before["misses"]
    # and with the process's in-memory executables dropped, as a second
    # process would ask: the specs lower to the module of the first
    # call, so the persistent cache answers
    jax.clear_caches()
    assert cc.scope_map("train_dispatch") == modules
    after = cc.compile_cache_stats()
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]
    table = modules["jit_multi_fn"]
    chains = {scopes.scope_of(op_name) for op_name in table.values()}
    innermost = {chain[-1] for chain in chains if chain}
    assert {scopes.BATCH_GATHER, scopes.AUG_FIXED, scopes.MODEL,
            scopes.OPTIMIZER, scopes.aug_op("Equalize")} <= innermost
    assert any(scopes.is_backward(op_name) for op_name in table.values())
    # a fusion is named by the scope of what it computes
    fusions = [name for name in table if "fusion" in name]
    assert fusions
    assert sum(bool(scopes.scope_of(table[n])) for n in fusions) > len(fusions) // 2


def test_first_call_keeps_specs_and_no_arrays(called_train_dispatch):
    multi, _ = called_train_dispatch
    args, kwargs = multi._first_call_specs
    assert kwargs == {}
    leaves = jax.tree.leaves(args)
    assert leaves and all(isinstance(leaf, jax.ShapeDtypeStruct)
                          for leaf in leaves)
    assert all(leaf.sharding is not None for leaf in leaves)


def test_uncommitted_and_numpy_arguments_lower_to_the_same_module():
    """The specs reproduce committed-ness: an uncommitted array's spec
    carries no sharding, a numpy array's neither, and the map's
    lowering is the one the first call cached."""
    fn = cc.seam_jit(lambda x, y, n: _scoped_add(x, y) * n,
                     label="t_scope_uncommitted")
    fn(jnp.ones((8,)), np.ones((8,), np.float32), 3)
    args, _ = fn._first_call_specs
    assert args[0].sharding is None and args[1].sharding is None
    assert args[2] == 3
    before = cc.compile_cache_stats()["misses"]
    modules = cc.scope_map("t_scope_uncommitted")
    assert cc.compile_cache_stats()["misses"] == before
    assert len(modules) == 1


def _scoped_add(x, y):
    with jax.named_scope(scopes.OPTIMIZER):
        return x + y


def test_scope_map_asks_nothing_of_a_label_never_called():
    cc.seam_jit(lambda x: x + 1, label="t_scope_never_called")
    assert cc.scope_map("t_scope_never_called") == {}


def test_scope_map_pins_only_the_newest_of_a_label():
    import gc

    first = cc.seam_jit(lambda x: _scoped_add(x, x), label="t_scope_pin")
    first(jnp.ones((4,)))
    second = cc.seam_jit(lambda x: _scoped_add(x, x) + 1.0, label="t_scope_pin")
    second(jnp.ones((3,)))
    assert len(cc.scope_map("t_scope_pin")) >= 1
    del first, second
    gc.collect()
    # the trainer has returned: the program it ran last is still found
    tables = cc.scope_map("t_scope_pin")
    assert len(tables) == 1
    assert len(cc._called["t_scope_pin"]) == 1


def test_stale_cache_raises_a_named_error():
    """A module without a single scope: what a cache warmed before the
    scopes were added hands back.  The message names the cache."""
    fn = cc.seam_jit(lambda x: x * 2 + 1, label="t_scope_stale")
    fn(jnp.ones((8,)))
    with pytest.raises(cc.ScopeMapError) as err:
        cc.scope_map("t_scope_stale")
    assert "t_scope_stale" in str(err.value)
    assert str(cc.cache_dir()) in str(err.value)


# ----------------------------------------------------------- the parser

HLO_TEXT = """\
HloModule jit_multi_fn, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "/root/repo/fast_autoaugment_tpu/train/steps.py"

%fused_computation.5 (param_0.1: f32[8], param_1.2: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %param_1.2 = f32[8]{0} parameter(1)
  %add.3 = f32[8]{0} add(%param_0.1, %param_1.2), metadata={op_name="jit(multi_fn)/vmap(faa_aug_policy)/faa_aug_op_Equalize/add" stack_frame_id=3}
  %mul.4 = f32[8]{0} multiply(%add.3, %add.3), metadata={op_name="jit(multi_fn)/vmap(faa_aug_policy)/faa_aug_op_Equalize/mul" stack_frame_id=3}
  ROOT %sub.5 = f32[8]{0} subtract(%mul.4, %param_0.1), metadata={op_name="jit(multi_fn)/vmap(faa_aug_fixed)/sub" stack_frame_id=4}
}

%region_0.7 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%while_body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fusion.11 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.5
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%p, %fusion.11)
}

%while_cond.1 (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%p.1, %p.1), direction=LT
}

%fused_computation.48 (param_0.9: f32[8]) -> f32[8] {
  %param_0.9 = f32[8]{0} parameter(0)
  ROOT %dynamic-slice.250 = f32[8]{0} dynamic-slice(%param_0.9), dynamic_slice_sizes={8}
}

%wide.while_body.32 (wide.param.2: (s32[], f32[8])) -> (s32[], f32[8]) {
  %wide.param.2 = (s32[], f32[8]{0}) parameter(0)
  %constant_dynamic-slice_fusion.7 = f32[8]{0} fusion(%wide.param.2), kind=kLoop, calls=%fused_computation.48
  %dynamic-update-slice.97 = f32[8]{0} dynamic-update-slice(%wide.param.2, %constant_dynamic-slice_fusion.7)
  ROOT %tuple.830 = (s32[], f32[8]{0}) tuple(%wide.param.2, %dynamic-update-slice.97)
}

ENTRY %main.20 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.2361 = f32[8]{0} fusion(%Arg_0.1, %Arg_0.1), kind=kCustom, calls=%fused_computation.5
  %while.3 = (s32[], f32[8]{0}) while(%fusion.2361), condition=%while_cond.1, body=%while_body.1
  %reduce.1 = f32[] reduce(%fusion.2361, %Arg_0.1), dimensions={0}, to_apply=%region_0.7, metadata={op_name="jit(multi_fn)/transpose(jvp(faa_model))/reduce_sum"}
  %copy.7 = f32[8]{0} copy(%fusion.2361)
  %while.58 = (s32[], f32[8]{0}) while(%while.3), condition=%while_cond.1, body=%wide.while_body.32, metadata={op_name="jit(multi_fn)/vmap(faa_aug_fixed)/gather" stack_frame_id=53}
  ROOT %fusion.9 = f32[8]{0} fusion(%copy.7, %Arg_0.1), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(multi_fn)/faa_optimizer/add"}
}
"""


def test_parse_scope_map_reads_every_computation_and_votes_for_fusions():
    module, table = cc.parse_scope_map(HLO_TEXT)
    assert module == "jit_multi_fn"
    # instructions of fused computations and loop bodies are in the map
    assert scopes.scope_of(table["add.3"])[-1] == "faa_aug_op_Equalize"
    assert scopes.scope_of(table["sub.5"]) == ("faa_aug_fixed",)
    # a fusion without a name of its own takes the commonest scope chain
    # of what it calls (two Equalize instructions against one fixed)
    assert scopes.scope_of(table["fusion.2361"])[-1] == "faa_aug_op_Equalize"
    # through a loop body too
    assert scopes.scope_of(table["fusion.11"])[-1] == "faa_aug_op_Equalize"
    assert scopes.scope_of(table["while.3"])[-1] == "faa_aug_op_Equalize"
    # its own name wins where it has one; the backward pass is kept
    assert scopes.scope_of(table["fusion.9"]) == ("faa_optimizer",)
    assert scopes.is_backward(table["reduce.1"])
    # a loop the compiler made: the while is named, its body is not, and
    # takes the caller's name, down into the body's own fusions
    for name in ("dynamic-update-slice.97", "constant_dynamic-slice_fusion.7",
                 "dynamic-slice.250", "tuple.830"):
        assert scopes.scope_of(table[name]) == ("faa_aug_fixed",), name
    # after the vote: while.3 was named by what it calls, and names the
    # rest of its body and its condition (which the first caller keeps)
    assert scopes.scope_of(table["tuple.2"])[-1] == "faa_aug_op_Equalize"
    assert scopes.scope_of(table["lt.1"])[-1] == "faa_aug_op_Equalize"
    # nothing to inherit from: unscoped, but in the map
    assert table["copy.7"] == "" and table["Arg_0.1"] == "x"


def test_parse_scope_members_finds_a_scope_in_a_fusion_rooted_elsewhere():
    """By membership, not by root: ``fused_computation.5`` is rooted in
    ``faa_aug_fixed`` and holds two Equalize instructions, so every
    fusion that calls it holds all three scopes, whatever it is named."""
    module, held = cc.parse_scope_members(HLO_TEXT)
    assert module == "jit_multi_fn"
    inside = ("faa_aug_fixed", "faa_aug_op_Equalize", "faa_aug_policy")
    assert held["fusion.2361"] == inside and held["fusion.11"] == inside
    # its own name is counted with what it calls
    assert held["fusion.9"] == tuple(sorted(inside + ("faa_optimizer",)))
    # through a loop: body and condition, however deep
    assert held["while.3"] == inside
    assert held["while.58"] == ("faa_aug_fixed",)
    assert held["reduce.1"] == ("faa_model",)
    # a single instruction holds its own chain; one without a scope is left out
    assert held["add.3"] == ("faa_aug_op_Equalize", "faa_aug_policy")
    assert "copy.7" not in held and "dynamic-slice.250" not in held
    # where the two readings part: the map files fusion.2361 under Equalize alone
    _, table = cc.parse_scope_map(HLO_TEXT)
    assert "faa_aug_fixed" not in scopes.scope_of(table["fusion.2361"])


def test_scope_members_reads_the_compiled_program_of_a_label():
    def multi_fn(x):
        with jax.named_scope(scopes.MODEL):
            y = jnp.sin(x)
            with jax.named_scope(scopes.SHAKE_MIX):
                return y * 2.0

    cc.seam_jit(multi_fn, label="t_scope_members")(jnp.arange(8.0))
    held = cc.scope_members("t_scope_members")["jit_multi_fn"]
    assert (scopes.MODEL, scopes.SHAKE_MIX) in set(held.values())
    assert set(held) <= set(cc.scope_map("t_scope_members")["jit_multi_fn"])
    assert cc.scope_members("t_scope_no_such_label") == {}


def test_parse_scope_map_takes_names_without_the_percent_sign():
    module, table = cc.parse_scope_map(HLO_TEXT.replace("%", ""))
    assert module == "jit_multi_fn"
    assert scopes.scope_of(table["fusion.2361"])[-1] == "faa_aug_op_Equalize"
    assert scopes.scope_of(table["fusion.9"]) == ("faa_optimizer",)


# ------------------------------------------- the host-fed (ImageNet) step

HOSTFED_TABLE = (scopes.AUG_POLICY, scopes.AUG_FIXED, scopes.AUG_JITTER,
                 scopes.AUG_LIGHTING, scopes.MODEL, scopes.RESNET_STEM,
                 scopes.LOSS, scopes.OPTIMIZER, scopes.METRICS)


@pytest.fixture(scope="module")
def lowered_hostfed_step():
    """The single-step program ``train_and_eval`` builds for a lazy data
    set (``make_train_step`` with ``imagenet_train_batch`` as its
    ``augment_fn``), lowered on a ResNet-50 at 32 px with a two-row policy."""
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.ops.preprocess_imagenet import imagenet_train_batch
    from fast_autoaugment_tpu.train.steps import create_train_state, make_train_step

    model = get_model({"type": "resnet50", "dataset": "imagenet"}, 1000)
    opt = build_optimizer({"type": "sgd", "decay": 1e-4, "nesterov": True},
                          lambda s: 0.05)
    step = make_train_step(
        model, opt, num_classes=1000, cutout_length=0, use_policy=True,
        augment_fn=lambda images, policy, key: imagenet_train_batch(
            images, key, policy, cutout_length=0))
    state = jax.eval_shape(lambda: create_train_state(
        model, opt, jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3)), False))
    return step.lower(
        state, jax.ShapeDtypeStruct((4, 32, 32, 3), jnp.uint8),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((2, 2, 3), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)


@pytest.mark.parametrize("name", HOSTFED_TABLE)
def test_a_scope_of_the_hostfed_step_reaches_its_lowering(
        lowered_hostfed_step, name):
    assert name in lowered_hostfed_step


def test_the_new_scopes_nest_where_the_readers_look(lowered_hostfed_step):
    """Jitter and Lighting inside ``faa_aug_fixed`` (so ``aug_fixed_device_ms``
    holds them), the stem inside ``faa_model``, forward and backward, and
    the policy's operations inside ``faa_aug_policy`` as on the CIFAR step."""
    text = lowered_hostfed_step
    assert f"vmap({scopes.AUG_FIXED})/{scopes.AUG_JITTER}/" in text
    assert f"vmap({scopes.AUG_FIXED})/{scopes.AUG_LIGHTING}/" in text
    assert f"jvp({scopes.MODEL})/ResNet/{scopes.RESNET_STEM}/" in text
    assert f"transpose(jvp({scopes.MODEL}))/ResNet/{scopes.RESNET_STEM}/" in text
    assert f"vmap({scopes.AUG_POLICY})/" in text
    for name in (scopes.AUG_JITTER, scopes.AUG_LIGHTING, scopes.RESNET_STEM):
        assert name.startswith(scopes.PREFIX)
        chain = scopes.scope_of(f"jit(step_fn)/vmap(faa_aug_fixed)/{name}/add")
        assert chain == ("faa_aug_fixed", name)
