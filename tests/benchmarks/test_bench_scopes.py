"""The step program's device time by named scope
(``benchmarks/harness/scopes.py`` and the nine readers over it): on
hand-made events where every number can be worked out by hand, and on an
excerpt recorded on the chip (``benchmarks/testdata/v5e_train_step_scopes.json``:
events of the step program with the part of the program's scope map that
names them)."""

import json
import os

import pytest

from benchmarks.harness import scopes as hs
from benchmarks.harness import trace as tr
from benchmarks.harness.observed import Observed, TraceView
from benchmarks.harness.spec import BENCH_DIR, load_json, load_module, resolve_cell
from fast_autoaugment_tpu.core import compilecache, scopes

READERS = ("aug_policy_device_ms", "aug_histogram_ops_device_ms",
           "aug_geometric_ops_device_ms", "aug_fixed_device_ms",
           "model_forward_device_ms", "model_backward_device_ms",
           "optimizer_device_ms", "batch_gather_device_ms")
SIX = ("aug_policy_device_ms", "aug_fixed_device_ms", "model_forward_device_ms",
       "model_backward_device_ms", "optimizer_device_ms",
       "batch_gather_device_ms")

JIT = "jit(multi_fn)/"
MODULES = {"jit_multi_fn": {
    "fusion.1": JIT + "faa_batch_gather/jit(_take)/gather",
    "while.2": JIT + "vmap(faa_aug_policy)/faa_aug_op_Equalize/while",
    "fusion.3": JIT + "vmap(faa_aug_policy)/faa_aug_op_Equalize/sort",
    "fusion.4": JIT + "vmap(faa_aug_policy)/faa_aug_op_Rotate/gather",
    "fusion.5": JIT + "vmap(faa_aug_policy)/select_n",
    "fusion.6": JIT + "vmap(faa_aug_fixed)/dynamic_slice",
    "fusion.7": JIT + "jvp(faa_model)/WideResNet/conv1/conv_general_dilated",
    "fusion.8": JIT + "jvp(faa_loss)/reduce_sum",
    "fusion.9": JIT + "transpose(jvp(faa_model))/WideResNet/conv1/conv_general_dilated",
    "fusion.10": JIT + "faa_optimizer/add",
    "fusion.11": JIT + "faa_metrics/top_k",
    "copy.12": "",
    "fusion.13": JIT + "jit(_threefry_split)/threefry2x32",
    # the resampling since PR 29: once an op slot under the policy, and (a
    # form no program has today) inside an operation's own scope
    "fusion.14": JIT + "vmap(faa_aug_policy)/faa_aug_warp/dot_general",
    "fusion.15": JIT + "vmap(faa_aug_policy)/faa_aug_op_Rotate/faa_aug_warp/dot_general",
}}


#: the rotate's matrix and the two resamplings: 150 ns of an execution
ROTATE_AND_WARPS = ("fusion.4", "fusion.14", "fusion.15")


def _step(t0, scale=1.0):
    """One execution of 1,000 ns x `scale` from `t0`: a gather of 10, a
    while of 300 that holds a sort of 200 and (not of its loop, but
    inside its span) nothing else, a rotate of 100 (its matrix), the
    policy's resampling of 35 and one of 15 inside the rotate's scope, a
    select of 40, the fixed stack 60, forward 100 + loss 10, backward 180, optimizer 20 +
    metrics 5, an unscoped copy of 30, an unscoped threefry of 15, an
    instruction the map has never seen of 25, and 55 in which nothing
    runs."""
    rows = [("%fusion.1 = u8[8,32,32,3]{3,2,1,0} fusion(u8[64,32,32,3] %p), kind=kLoop", 0, 10),
            ("%while.2 = (s32[], s32[2056]) while((s32[], s32[2056]) %t), body=%b", 10, 300),
            ("%fusion.3 = s32[2056]{0} fusion(s32[8,1024] %a), kind=kCustom", 60, 200),
            ("%fusion.4 = f32[8192,3]{1,0} fusion(f32[8,32,32,3] %a), kind=kCustom", 310, 100),
            ("%fusion.14 = f32[8,1024,3]{2,1,0} fusion(f32[8,32,96] %a), kind=kOutput", 410, 35),
            ("%fusion.15 = f32[8,1024,3]{2,1,0} fusion(f32[8,32,96] %a), kind=kOutput", 445, 15),
            ("%fusion.5 = f32[8,32,32,3]{3,2,1,0} fusion(f32[8,32,32,3] %a), kind=kLoop", 460, 40),
            ("%fusion.6 = f32[8,32,32,3]{3,2,1,0} fusion(f32[8,32,32,3] %a), kind=kLoop", 500, 60),
            ("%fusion.7 = f32[8,32,32,16]{3,2,1,0} fusion(f32[8,32,32,3] %a), kind=kOutput", 560, 100),
            ("%fusion.8 = f32[]{:T(128)} fusion(f32[8,10] %a), kind=kLoop", 660, 10),
            ("%fusion.9 = f32[3,3,3,16]{3,2,1,0} fusion(f32[8,32,32,16] %a), kind=kOutput", 670, 180),
            ("%fusion.10 = f32[3,3,3,16]{3,2,1,0} fusion(f32[3,3,3,16] %a), kind=kLoop", 850, 20),
            ("%fusion.11 = f32[]{:T(128)} fusion(f32[8,10] %a), kind=kLoop", 870, 5),
            ("%copy.12 = f32[8]{0} copy(f32[8] %a)", 875, 30),
            ("%fusion.13 = u32[8,2]{1,0} fusion(u32[2] %k), kind=kLoop", 905, 15),
            ("%fusion.99 = f32[8]{0} fusion(f32[8] %a), kind=kLoop", 920, 25)]
    return [(name, t0 + s * scale, d * scale) for name, s, d in rows]


def _plane(scales=(1.0, 1.0, 3.0, 1.0)):
    """Executions back to back with 10 ns between them; the first and the
    last are left out, as ``step_device_ms`` leaves them out."""
    ops, runs, t0 = [], [], 0.0
    for scale in scales:
        ops += _step(t0, scale)
        runs.append(("jit_multi_fn(9872292373413704833)", t0, 1000.0 * scale))
        t0 += 1000.0 * scale + 10.0
    ops.append(("%add.1 = s32[]{:T(128)} add(s32[] %x, s32[] %y)", t0, 5.0))
    runs.append(("jit_bench_marker(1)", t0, 5.0))

    def events(rows):
        return sorted((tr.Event(n, float(s), float(d)) for n, s, d in rows),
                      key=lambda e: (e.start_ns, -e.dur_ns))
    return tr.Plane("/device:TPU:0", [tr.Line(tr.OPS_LINE, events(ops)),
                                      tr.Line(tr.MODULES_LINE, events(runs))])


def test_names_are_cut_from_the_trace_events():
    assert hs.instruction_name(
        "%fusion.2361 = s32[526336]{0:T(1024)S(1)} fusion(s32[2048,1024] %g), "
        "kind=kCustom, calls=%fused_computation.5") == "fusion.2361"
    assert hs.instruction_name("while.2 = (s32[]) while(...)") == "while.2"
    assert hs.module_name("jit_multi_fn(9872292373413704833)") == "jit_multi_fn"
    assert hs.scope_key(MODULES["jit_multi_fn"]["fusion.9"], scopes) == "faa_model/backward"
    # the whole chain, outermost first
    equalize = "faa_aug_policy/faa_aug_op_Equalize"
    assert hs.scope_key(MODULES["jit_multi_fn"]["fusion.3"], scopes) == equalize
    assert hs.split_key(equalize) == (("faa_aug_policy", "faa_aug_op_Equalize"), False)
    assert hs.split_key("faa_model/backward") == (("faa_model",), True)
    # a nested jit repeats the path (recorded: Equalize's searchsorted, six deep)
    assert hs.scope_key(JIT + "vmap(faa_aug_policy)/faa_aug_op_Equalize/jit(searchsorted)/"
                        + JIT + "vmap(faa_aug_policy)/faa_aug_op_Equalize/gather",
                        scopes) == equalize
    assert hs.scope_key("", scopes) == hs.scope_key(None, scopes) == hs.UNSCOPED


def test_scopes_and_unscoped_add_up_to_the_steps_device_time():
    split = hs.split_plane(_plane(), "^jit_multi_fn", MODULES, scopes)
    assert split.durations_ns == [1000.0, 3000.0]  # the middle two of four
    one = split.executions[0]
    assert sum(one.values()) == pytest.approx(1000.0)
    # nested time counts once: the while keeps 300 - 200 for itself
    assert one["faa_aug_policy/faa_aug_op_Equalize"] == pytest.approx(300.0)
    assert one["faa_aug_policy/faa_aug_op_Rotate"] == 100.0
    assert one["faa_aug_policy/faa_aug_warp"] == 35.0
    assert one["faa_aug_policy/faa_aug_op_Rotate/faa_aug_warp"] == 15.0
    assert one["faa_aug_policy"] == 40.0
    assert one["faa_aug_fixed"] == 60.0 and one["faa_batch_gather"] == 10.0
    # backward splits from forward
    assert one["faa_model"] == 100.0 and one["faa_model/backward"] == 180.0
    assert one["faa_loss"] == 10.0
    assert one["faa_optimizer"] == 20.0 and one["faa_metrics"] == 5.0
    # no scope (30 + 15), not in the map (25), nothing running (55)
    assert one[hs.UNSCOPED] == pytest.approx(125.0)
    assert split.unscoped_ops == {"copy f32[8]": pytest.approx(30.0 * 4),
                                  "fusion:kLoop u32[8,2]": pytest.approx(15.0 * 4),
                                  "fusion:kLoop f32[8]": pytest.approx(25.0 * 4)}
    assert split.unscoped_share() == pytest.approx(12.5)
    # a metric is the median over executions (1x and 3x: 2x)
    fam = hs.families(scopes)
    assert split.median_ms(fam["policy"]) == pytest.approx(2 * 490e-6)
    assert split.median_ms(fam["histogram_ops"]) == pytest.approx(2 * 300e-6)
    # the seven matrices (100 + the 15 nested in one) and the resampling
    # they drive (35): all of it the policy's in the partition, counted once
    assert split.median_ms(fam["geometric_ops"]) == pytest.approx(2 * 150e-6)
    assert split.median_ms(lambda k: scopes.AUG_WARP in hs.split_key(k)[0]) == \
        pytest.approx(2 * 50e-6)
    assert all(fam["policy"](k) and not fam["histogram_ops"](k)
               for k in one if scopes.AUG_WARP in k)
    assert split.median_ms(fam["forward"]) == pytest.approx(2 * 110e-6)
    assert split.median_ms(fam["backward"]) == pytest.approx(2 * 180e-6)
    assert split.median_ms(fam["optimizer"]) == pytest.approx(2 * 25e-6)
    six = sum(split.median_ms(fam[f]) for f in hs.PARTITION)
    assert six + split.median_ms(lambda k: k == hs.UNSCOPED) == pytest.approx(2 * 1000e-6)


def test_an_unknown_module_is_all_unscoped():
    split = hs.split_plane(_plane(), "^jit_multi_fn", {}, scopes)
    assert split.unscoped_share() == pytest.approx(100.0)
    assert hs.split_plane(_plane(), "^jit_no_such", MODULES, scopes).executions == []


# What a model names inside ``faa_model`` (the next configuration's
# ShakeDrop gate): the threefry becomes its forward draw, the instruction
# the map had never seen its backward mix, the copy a scope no family names
NESTED = {"jit_multi_fn": dict(MODULES["jit_multi_fn"], **{
    "fusion.13": JIT + "jvp(faa_model)/PyramidNet/block3/faa_shake_drop/mul",
    "fusion.99": JIT + "transpose(jvp(faa_model))/PyramidNet/block3/faa_shake_drop/mul",
    "copy.12": JIT + "faa_not_of_any_family/copy"})}


@pytest.mark.parametrize("family, flat_ns, nested_ns", [
    ("forward", 110.0, 125.0), ("backward", 180.0, 205.0),
    ("policy", 490.0, 490.0), ("optimizer", 25.0, 25.0)])
def test_a_scope_nested_under_the_model_stays_the_models(family, flat_ns, nested_ns):
    fam = hs.families(scopes)
    flat = hs.split_plane(_plane(), "^jit_multi_fn", MODULES, scopes)
    nested = hs.split_plane(_plane(), "^jit_multi_fn", NESTED, scopes)
    assert flat.median_ms(fam[family]) == pytest.approx(2 * flat_ns * 1e-6)
    assert nested.median_ms(fam[family]) == pytest.approx(2 * nested_ns * 1e-6)


def test_the_partition_holds_with_nested_scopes():
    nested = hs.split_plane(_plane(), "^jit_multi_fn", NESTED, scopes)
    one = nested.executions[0]
    assert one["faa_model/faa_shake_drop"] == 15.0
    assert one["faa_model/faa_shake_drop/backward"] == 25.0
    assert one["faa_model"] == 100.0 and one["faa_model/backward"] == 180.0
    # a scope that no family names is scoped time in nobody's metric
    assert one["faa_not_of_any_family"] == 30.0
    assert one[hs.UNSCOPED] == pytest.approx(55.0)
    fam = hs.families(scopes)
    for parts, total in zip(nested.executions, nested.durations_ns):
        six = sum(ns for k, ns in parts.items()
                  if any(fam[f](k) for f in hs.PARTITION))
        assert six + parts[hs.UNSCOPED] + parts["faa_not_of_any_family"] == \
            pytest.approx(total)
    # each key is in one family of the six at most
    assert all(sum(fam[f](k) for f in hs.PARTITION) <= 1
               for parts in nested.executions for k in parts)


def _observed(plane, monkeypatch, tmp_path, modules=MODULES, raises=None):
    cell = resolve_cell("wrn40x2_train", trace=True)
    obs = Observed(cell=cell, devices=[], end_to_end={}, window_s=1.0,
                   attempted=0, failed=0, checks={}, compile_stats={},
                   memory_peak_bytes=0, step_program=cell.traffic["step_program"],
                   trace_dir=str(tmp_path))
    obs.__dict__["trace"] = TraceView([plane], tr.traced_window([plane]), None)
    asked = []

    def scope_map(label):
        asked.append(label)
        if raises is not None:
            raise raises
        return modules

    monkeypatch.setattr(compilecache, "scope_map", scope_map)
    return obs, asked


def test_nine_readers_one_map_and_a_file_beside_the_trace(monkeypatch, tmp_path):
    obs, asked = _observed(_plane(), monkeypatch, tmp_path)
    values = {name: load_module("layer_metrics", name).read(obs) for name in READERS}
    share = load_module("layer_metrics", "step_unscoped_share").read(obs)
    assert asked == ["train_dispatch"]  # lowered once for nine readers
    assert values["aug_policy_device_ms"] == pytest.approx(2 * 490e-6)
    assert values["aug_fixed_device_ms"] == pytest.approx(2 * 60e-6)
    assert values["batch_gather_device_ms"] == pytest.approx(2 * 10e-6)
    assert share == pytest.approx(12.5)
    step_ms = load_module("layer_metrics", "step_device_ms").read(obs)
    assert sum(values[n] for n in SIX) + share / 100 * step_ms == pytest.approx(step_ms)
    # the reader takes the resampling with the matrices (PR 34)
    assert values["aug_geometric_ops_device_ms"] == pytest.approx(2 * 150e-6)
    assert hs.scope_ms(obs, scopes.AUG_WARP) == pytest.approx(2 * 50e-6)
    assert (values["aug_histogram_ops_device_ms"] + values["aug_geometric_ops_device_ms"]
            <= values["aug_policy_device_ms"])
    held = load_json(hs.map_path(str(tmp_path), "train_dispatch"))
    assert held == {"label": "train_dispatch", "step_program": "^jit_multi_fn",
                    "modules": MODULES}


@pytest.mark.parametrize("name, backward, ns", [
    ("faa_shake_drop", None, 40.0), ("faa_shake_drop", False, 15.0),
    ("faa_shake_drop", True, 25.0), ("faa_model", None, 320.0),
    ("faa_aug_op_Equalize", None, 300.0), ("faa_aug_policy", None, 490.0),
    ("faa_aug_warp", None, 50.0), ("faa_aug_op_Rotate", None, 115.0),
    ("faa_no_such_scope", None, 0.0)])
def test_scope_ms_reads_a_scope_wherever_it_is_nested(monkeypatch, tmp_path,
                                                      name, backward, ns):
    """What a later reader file calls: three lines, no edit to the harness."""
    obs, _ = _observed(_plane(), monkeypatch, tmp_path, modules=NESTED)
    assert hs.scope_ms(obs, name, backward) == pytest.approx(2 * ns * 1e-6)


def test_the_models_readers_hold_what_is_nested_and_the_step_adds_up(
        monkeypatch, tmp_path):
    obs, _ = _observed(_plane(), monkeypatch, tmp_path, modules=NESTED)
    values = {n: load_module("layer_metrics", n).read(obs) for n in SIX}
    assert values["model_forward_device_ms"] == pytest.approx(2 * 125e-6)
    assert values["model_backward_device_ms"] == pytest.approx(2 * 205e-6)
    step_ms = load_module("layer_metrics", "step_device_ms").read(obs)
    share = load_module("layer_metrics", "step_unscoped_share").read(obs)
    # the six, the unscoped and the one scope of no family make the step
    assert (sum(values.values()) + share / 100 * step_ms
            + hs.scope_ms(obs, "faa_not_of_any_family")) == pytest.approx(step_ms)


def test_scope_ms_reports_nothing_where_the_split_does_not_hold(monkeypatch,
                                                                tmp_path):
    obs, _ = _observed(_plane(), monkeypatch, tmp_path)
    obs.__dict__["trace"] = None
    assert hs.scope_ms(obs, "faa_model") is None
    fewer = {"jit_multi_fn": {k: v for k, v in NESTED["jit_multi_fn"].items()
                              if k not in ROTATE_AND_WARPS}}  # 20.5% unexplained
    obs, _ = _observed(_plane(), monkeypatch, tmp_path, modules=fewer)
    assert hs.scope_ms(obs, "faa_shake_drop") is None


def test_the_table_prints_a_nested_scope_under_its_parent():
    nested = hs.split_plane(_plane(), "^jit_multi_fn", NESTED, scopes)
    lines = hs.format_table("train_dispatch", nested, scopes)
    at = {line.split()[0]: i for i, line in enumerate(lines)
          if line.startswith("    ")}
    indent = {line.split()[0]: len(line) - len(line.lstrip()) for line in lines
              if line.startswith("    ")}
    assert at["faa_model"] < at["faa_model/backward"] < at["faa_shake_drop"] \
        < at["faa_shake_drop/backward"] < at["faa_optimizer"]
    assert indent["faa_shake_drop"] == indent["faa_model"] + 2
    assert indent["faa_aug_op_Equalize"] == indent["faa_aug_policy"] + 2
    assert indent["faa_aug_op_Invert"] == indent["faa_aug_policy"] + 2  # reads 0


def test_a_split_that_leaves_a_fifth_unexplained_is_no_split(monkeypatch, tmp_path):
    fewer = {"jit_multi_fn": {k: v for k, v in MODULES["jit_multi_fn"].items()
                              if k not in ROTATE_AND_WARPS}}  # 150 more: 27.5%
    obs, _ = _observed(_plane(), monkeypatch, tmp_path, modules=fewer)
    assert all(load_module("layer_metrics", n).read(obs) is None for n in READERS)
    assert load_module("layer_metrics", "step_unscoped_share").read(obs) == \
        pytest.approx(27.5)


def test_readers_report_nothing_where_there_is_nothing_to_read(monkeypatch,
                                                                 tmp_path, capsys):
    everything = READERS + ("step_unscoped_share",)
    # the map raises, and raises again compiled with the cache off
    obs, asked = _observed(_plane(), monkeypatch, tmp_path,
                           raises=compilecache.ScopeMapError("stale: /x/.jax_cache"))
    assert all(load_module("layer_metrics", n).read(obs) is None for n in everything)
    assert asked == ["train_dispatch"] * 2  # once each, for nine readers
    assert "stale: /x/.jax_cache" in capsys.readouterr().err
    assert not os.path.exists(hs.map_path(str(tmp_path), "train_dispatch"))
    # an untraced run, and a trace without the program
    obs, asked = _observed(_plane(), monkeypatch, tmp_path)
    obs.__dict__["trace"] = None
    assert all(load_module("layer_metrics", n).read(obs) is None for n in everything)
    assert asked == []
    obs, _ = _observed(_plane(scales=(1.0, 1.0)), monkeypatch, tmp_path)
    assert all(load_module("layer_metrics", n).read(obs) is None for n in everything)
    # a program from before the scopes has no table of names
    obs, asked = _observed(_plane(), monkeypatch, tmp_path)
    monkeypatch.setattr(hs, "program_scopes", lambda: None)
    assert all(load_module("layer_metrics", n).read(obs) is None for n in everything)
    assert asked == []


def test_a_cache_warmed_before_the_scopes_is_compiled_past(tmp_path):
    """Parent and change measured in turn over one cache directory: the
    change's step comes back with the parent's metadata, the program's
    map raises its named error, and the reader compiles once more with
    the cache off, then leaves the cache as it found it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as jax_cc

    def make(scoped):
        def multi_fn(x):
            if not scoped:
                return jnp.sin(x) * 2.0
            with jax.named_scope(scopes.OPTIMIZER):
                return jnp.sin(x) * 2.0
        return compilecache.seam_jit(multi_fn, label="t_bench_stale")

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax_cc.reset_cache()
    compilecache.configure_compile_cache()
    try:
        x = jnp.arange(8.0)
        make(scoped=False)(x)            # the parent fills the cache
        misses = compilecache.compile_cache_stats()["misses"]
        make(scoped=True)(x)             # the change hits it
        assert compilecache.compile_cache_stats()["misses"] == misses
        with pytest.raises(compilecache.ScopeMapError, match="cache"):
            compilecache.scope_map("t_bench_stale")
        cell = resolve_cell("wrn40x2_train", trace=True)
        obs = Observed(cell=cell, devices=[], end_to_end={}, window_s=1.0,
                       attempted=0, failed=0, checks={}, compile_stats={},
                       memory_peak_bytes=0, trace_dir=str(tmp_path))
        modules = hs._scope_modules(obs, "t_bench_stale")
        assert {scopes.scope_of(v) for v in modules["jit_multi_fn"].values()
                } >= {(scopes.OPTIMIZER,)}
        assert jax.config.jax_enable_compilation_cache is True
        assert os.path.exists(hs.map_path(str(tmp_path), "t_bench_stale"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax_cc.reset_cache()
        compilecache.configure_compile_cache()


# ------------------------------------------------ recorded on the chip

RECORDED_PATH = os.path.join(BENCH_DIR, "testdata", "v5e_train_step_scopes.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED_PATH) as fh:
        held = json.load(fh)
    names = held["names"]
    planes = tr.planes_from_json([{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [[names[i], s, d] for i, s, d in ln["events"]]}
        for ln in p["lines"]]} for p in held["planes"]])
    return held, tr.device_planes(planes)[0]


def test_recorded_step_splits_into_the_programs_scopes(recorded):
    held, chip = recorded
    split = hs.split_plane(chip, held["step_program"], held["modules"], scopes)
    assert len(split.executions) == len(tr.program_runs(chip, held["step_program"])) - 2
    for parts, total in zip(split.executions, split.durations_ns):
        assert sum(parts.values()) == pytest.approx(total)
    fam = hs.families(scopes)
    ms = {f: split.median_ms(fam[f]) for f in fam}
    step_ms = tr.median([ns / 1e6 for ns in split.durations_ns])
    unscoped_ms = split.median_ms(lambda k: k == hs.UNSCOPED)
    assert sum(ms[f] for f in hs.PARTITION) + unscoped_ms == pytest.approx(
        step_ms, rel=0.01)
    for f, value in held["expected_ms"].items():
        assert ms[f] == pytest.approx(value, rel=1e-6), f
    assert split.unscoped_share() == pytest.approx(held["expected_unscoped_share"],
                                                   rel=1e-6)
    assert split.unscoped_share() < hs.MAX_UNSCOPED_SHARE
    # the augmentation is most of this step, the backward pass outweighs
    # the forward, and the two operation families lie inside the policy
    assert ms["policy"] > ms["forward"] + ms["backward"] > ms["optimizer"]
    assert ms["backward"] > ms["forward"] > 0
    assert ms["histogram_ops"] + ms["geometric_ops"] <= ms["policy"]


def test_recorded_table_names_every_operation(recorded):
    from fast_autoaugment_tpu.ops.augment import OP_NAMES

    held, chip = recorded
    split = hs.split_plane(chip, held["step_program"], held["modules"], scopes)
    lines = hs.format_table("train_dispatch", split, scopes)
    text = "\n".join(lines)
    for name in OP_NAMES:
        assert scopes.aug_op(name) in text
    for name in (scopes.BATCH_GATHER, scopes.AUG_POLICY, scopes.AUG_FIXED,
                 scopes.MODEL, scopes.MODEL + hs.BACKWARD, scopes.OPTIMIZER,
                 hs.UNSCOPED, "largest unscoped operations"):
        assert name in text
