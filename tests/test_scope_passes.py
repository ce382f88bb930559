"""The third pass and the map's staleness rule.

``core/scopes.py::pass_of`` on the real ``op_name`` strings of the five
recorded token steps (``benchmarks/testdata/v5e_*_step_scopes.json``): what
``nn.remat`` computes again sits under ``transpose(`` *and* under JAX's
``rematted_computation`` and reads ``recompute``; ``is_backward`` stays what
it was.  ``core/compilecache.py::scope_map`` refuses a compiled text that
holds a scope of this checkout's own lowering nowhere — the executable a
parent checkout left in the persistent cache, whose key leaves metadata out
— and takes the same text where this process compiled the program itself or
the cache is off."""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cc

from fast_autoaugment_tpu.core import compilecache as cc
from fast_autoaugment_tpu.core import scopes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILIES = ("kimi_linear", "glm4_moe_lite", "nemotron_h", "afmoe", "lfm2_moe")

HIDDEN = "Lfm2Moe.loss_terms/Lfm2Moe._hidden"
#: one real op_name of each shape, from the recorded step of lfm2_8b_a1b_train
SHAPES = [
    ("forward", f"jit(multi_fn)/jvp(faa_model)/{HIDDEN}/layer6/faa_short_conv/conv/"
                f"in_proj/dot_general", False),
    ("recompute", f"jit(multi_fn)/transpose(jvp(faa_model))/{HIDDEN}/jvp(faa_model)/"
                  f"{HIDDEN}/checkpoint/rematted_computation/layer2/faa_short_conv/conv/"
                  f"in_proj/dot_general", True),
    ("backward", f"jit(multi_fn)/transpose(jvp(faa_model))/{HIDDEN}/jvp(faa_model)/"
                 f"{HIDDEN}/checkpoint/layer2/faa_short_conv/conv/out_proj/dot_general",
     True),
]


def _op_names(family: str) -> list[str]:
    path = os.path.join(REPO, "benchmarks", "testdata", f"v5e_{family}_step_scopes.json")
    with open(path) as fh:
        modules = json.load(fh)["modules"]
    return [op_name for table in modules.values() for op_name in table.values()]


@pytest.mark.parametrize("which,op_name,backward", SHAPES, ids=[s[0] for s in SHAPES])
def test_pass_of_the_three_shapes(which, op_name, backward):
    assert op_name in _op_names("lfm2_moe")
    assert scopes.pass_of(op_name) == which
    assert scopes.is_backward(op_name) is backward
    assert which in scopes.PASSES


@pytest.mark.parametrize("family", FAMILIES)
def test_pass_of_on_every_recorded_op_name(family):
    """``recompute`` exactly where the path holds the component, ``backward``
    where ``is_backward`` and not that, ``forward`` else; all three occur, and
    whatever is computed again is backward to the older readers."""
    seen = {which: 0 for which in scopes.PASSES}
    for op_name in _op_names(family):
        which = scopes.pass_of(op_name)
        seen[which] += 1
        path = scopes._scoped_path(op_name)
        again = "/rematted_computation/" in path
        assert (which == "recompute") is again
        if again:
            assert scopes.is_backward(op_name) and scopes.MODEL in scopes.scope_of(op_name)
        else:
            assert (which == "backward") is scopes.is_backward(op_name)
    assert all(seen.values()), seen


def test_pass_of_reads_the_path_that_speaks_for_the_instruction():
    again = SHAPES[1][1]
    assert scopes.pass_of("") == "forward"
    assert scopes.pass_of("jit(multi_fn)/convert_element_type;" + again) == "recompute"
    # the first path that carries a scope speaks: an unscoped one that names the
    # component does not, nor a flax module that happens to be called like it
    assert scopes.pass_of("jit(f)/rematted_computation/add;jit(f)/faa_optimizer/add") == "forward"
    assert scopes.pass_of("jit(f)/jvp(faa_model)/my_rematted_computation_x/add") == "forward"
    assert scopes.pass_of("jit(multi_fn)/faa_optimizer/add") == "forward"
    assert scopes.pass_of("jit(multi_fn)/transpose(jvp(faa_loss))/mul") == "backward"


# ------------------------------------------------ the staleness of the map

OLD_COMPILED = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/jvp(faa_model)/layer1/faa_gqa/attn/q_proj/dot_general"}
}

ENTRY %main.3 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  ROOT %fusion.1 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(faa_model)/layer1/faa_gqa/attn/q_proj/dot_general"}
}
"""
NEW_COMPILED = OLD_COMPILED.replace("attn/q_proj", "attn/faa_mixer_proj/q_proj")
NEW_LOWERED = """\
module @jit_step {
  func.func public @main(%arg0: tensor<8xf32> loc("x")) -> tensor<8xf32> {
    %0 = stablehlo.multiply %arg0, %arg0 : tensor<8xf32> loc(#loc3)
    return %0 : tensor<8xf32> loc(#loc)
  } loc(#loc)
} loc(#loc)
#loc = loc(unknown)
#loc1 = loc("/root/repo/fast_autoaugment_tpu/models/token_blocks.py":116:15)
#loc3 = loc("jit(step)/jvp(faa_model)/layer1/faa_gqa/attn/faa_mixer_proj/q_proj/dot_general"(#loc1))
"""


def test_stale_scopes_are_the_lowerings_that_the_compiled_text_lacks():
    assert cc.stale_scopes(NEW_LOWERED, OLD_COMPILED) == {scopes.MIXER_PROJ}
    assert cc.stale_scopes(NEW_LOWERED, NEW_COMPILED) == set()
    # held anywhere: in a fused computation alone, or in a merged name's second half
    inside = NEW_COMPILED.replace(
        'kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(faa_model)'
        '/layer1/faa_gqa/attn/faa_mixer_proj/q_proj/dot_general"}',
        "kind=kLoop, calls=%fused_computation.1")
    assert inside != NEW_COMPILED and cc.stale_scopes(NEW_LOWERED, inside) == set()
    merged = OLD_COMPILED.replace(
        'attn/q_proj/dot_general"}', 'attn/q_proj/dot_general;jit(step)/jvp(faa_model)/'
        'layer1/faa_gqa/attn/faa_mixer_proj/q_proj/dot_general"}')
    assert cc.stale_scopes(NEW_LOWERED, merged) == set()
    # a file's path in a location names no scope
    assert cc.stale_scopes('#loc1 = loc("/root/faa_x/y.py":1:1)', OLD_COMPILED) == set()
    # a scope XLA folded away leaves no instruction named without it: a fact, not staleness
    folded = NEW_LOWERED + '#loc4 = loc("jit(step)/vmap(faa_aug_policy)/faa_aug_op_Posterize2/shift_left"(#loc1))\n'
    policy = NEW_COMPILED.replace("jit(step)/jvp(faa_model)/layer1/faa_gqa/attn/faa_mixer_proj/q_proj/dot_general\"}\n}",
                                  "jit(step)/vmap(faa_aug_policy)/faa_aug_op_Posterize/shift_left\"}\n}", 1)
    assert "faa_aug_op_Posterize/" in policy and scopes.MIXER_PROJ in policy
    assert cc.stale_scopes(folded, policy) == set()
    # ... unless the text names that instruction the way a checkout before the scope did
    before = policy.replace("faa_aug_op_Posterize/shift_left", "shift_left")
    assert cc.stale_scopes(folded, before) == {"faa_aug_op_Posterize2"}
    # ... where this lowering names nothing so itself; a scope whose every instruction has a
    # namesake outside it leaves nothing to look for, and the rule is the rule as it reads
    own = folded + '#loc5 = loc("jit(step)/vmap(faa_aug_policy)/shift_left"(#loc1))\n'
    assert cc.stale_scopes(own, before) == {"faa_aug_op_Posterize2"}
    assert cc.stale_scopes(own, policy) == {"faa_aug_op_Posterize2"}
    more = own + '#loc6 = loc("jit(step)/vmap(faa_aug_policy)/faa_aug_op_Posterize2/and"(#loc1))\n'
    assert cc.stale_scopes(more, before) == set()
    # the outermost scope is never a plain component: nothing to look for, so the rule as it reads
    assert cc.stale_scopes(NEW_LOWERED.replace("jvp(faa_model)", "jvp(faa_model)/faa_loss"),
                           OLD_COMPILED.replace("faa_model", "Model")) >= {"faa_model"}


class _Planted:
    """A lowering that says what it is told to."""

    def __init__(self, lowered: str, compiled: str):
        self._lowered, self._compiled = lowered, compiled

    def as_text(self, debug_info: bool = False) -> str:
        assert debug_info
        return self._lowered

    def compile(self):
        compiled = self._compiled

        class Compiled:
            @staticmethod
            def as_text() -> str:
                return compiled
        return Compiled()


@pytest.fixture()
def cache_switch():
    before = jax.config.jax_enable_compilation_cache
    yield lambda on: jax.config.update("jax_enable_compilation_cache", on)
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("compiled,cache_on,compiled_here,stale", [
    (OLD_COMPILED, True, False, True),     # the parent's executable, on a hit
    (OLD_COMPILED, False, False, False),   # the cache off: this checkout's compile
    (OLD_COMPILED, True, True, False),     # the first call was a miss: this process's
    (NEW_COMPILED, True, False, False),    # a hit on this checkout's own entry
], ids=["old_scopes_only", "cache_off", "compiled_here", "own_entry"])
def test_scope_map_on_planted_text(monkeypatch, cache_switch, compiled, cache_on,
                                   compiled_here, stale):
    monkeypatch.setattr(cc, "_lowerings", lambda label: [
        (_Planted(NEW_LOWERED, compiled), compiled_here)])
    cache_switch(cache_on)
    if stale:
        with pytest.raises(cc.ScopeMapError) as err:
            cc.scope_map("planted")
        assert scopes.MIXER_PROJ in str(err.value) and "planted" in str(err.value)
        assert "jit_step" in str(err.value)
    else:
        tables = cc.scope_map("planted")
        assert set(tables) == {"jit_step"}
        chain = scopes.scope_of(tables["jit_step"]["fusion.1"])
        assert (scopes.MIXER_PROJ in chain) is (compiled is NEW_COMPILED)


def test_a_module_without_a_scope_is_refused_with_the_cache_off_too(monkeypatch,
                                                                    cache_switch):
    bare = OLD_COMPILED.replace("faa_", "")
    monkeypatch.setattr(cc, "_lowerings", lambda label: [(_Planted(NEW_LOWERED, bare), True)])
    cache_switch(False)
    with pytest.raises(cc.ScopeMapError, match="carries a 'faa_' scope"):
        cc.scope_map("planted")


@pytest.fixture()
def own_cache(tmp_path):
    """A persistent cache of this test's own (process-wide state: the
    session's is restored after)."""
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax_cc.reset_cache()
    cc._reset_stats_for_tests()
    cc.configure_compile_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax_cc.reset_cache()
    cc._reset_stats_for_tests()
    cc.configure_compile_cache()


def test_a_parents_cached_executable_is_stale_and_a_compile_with_the_cache_off_is_not(
        own_cache, cache_switch):
    """The whole of C on real compiles: a 'parent' program fills the cache; the
    'change' differs by one scope, metadata alone, so its first call is a hit
    and the executable names the parent's scopes; the map refuses it by the
    scope's name, and compiled once more with the cache off — what
    ``benchmarks/harness/scopes.py::_scope_map_compiled_afresh`` does — it is
    this checkout's."""
    def program(inner):
        def step(x, w):
            with jax.named_scope(scopes.GQA):
                with inner():
                    y = x @ w
                return jnp.tanh(y)
        return step

    import contextlib
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    parent = cc.seam_jit(program(contextlib.nullcontext), label="t_stale_parent")
    parent(x, w)
    assert parent._first_call_verdict == "miss"
    assert scopes.MIXER_PROJ not in json.dumps(cc.scope_map("t_stale_parent"))
    change = cc.seam_jit(program(lambda: jax.named_scope(scopes.MIXER_PROJ)),
                         label="t_stale_change")
    jax.clear_caches()
    change(x, w)
    assert change._first_call_verdict == "hit"      # same key: metadata is left out
    with pytest.raises(cc.ScopeMapError, match=scopes.MIXER_PROJ):
        cc.scope_map("t_stale_change")
    cache_switch(False)
    jax_cc.reset_cache()
    jax.clear_caches()
    tables = cc.scope_map("t_stale_change")
    chains = {scopes.scope_of(op_name) for table in tables.values()
              for op_name in table.values()}
    assert (scopes.GQA, scopes.MIXER_PROJ) in chains
    cache_switch(True)
    jax_cc.reset_cache()
