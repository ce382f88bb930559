"""The step by scope and pass (``benchmarks/harness/passes.py``), the
projections' operations and bytes (``harness/projections.py``) and the three
readers PR 51 added — ``model_recompute_device_ms``, ``mixer_proj_device_ms``,
``mixer_proj_roofline`` — on recorded steps: the five token cells' older
excerpts (``benchmarks/testdata/v5e_*_step_scopes.json``, whose programs had
no ``faa_mixer_proj``) and one recorded from PR 51's own chip run of
``lfm2_8b_a1b_train`` (``v5e_lfm2_moe_step_passes.json``), which has."""

import json
import os
import types

import pytest

from benchmarks.harness import passes, projections, spec
from benchmarks.harness import scopes as hs
from benchmarks.harness import trace as tr
from benchmarks.harness.observed import Observed, TraceView
from fast_autoaugment_tpu.core import compilecache, scopes

CELLS = ("nemotron3_nano_30b_a3b_train", "trinity_mini_train", "lfm2_8b_a1b_train")
NEW_READERS = ("model_recompute_device_ms", "mixer_proj_device_ms", "mixer_proj_roofline")
#: family of a recorded excerpt: (the cell it was recorded in, the recompute pass's
#: median milliseconds on it, to the excerpt's rounding)
OLDER = {
    "lfm2_moe": ("lfm2_8b_a1b_train", 55.6),
    "nemotron_h": ("nemotron3_nano_30b_a3b_train", 58.5),
    "afmoe": ("trinity_mini_train", 162.4),
    "kimi_linear": ("kimi_linear_48b_a3b_train", 204.5),
    "glm4_moe_lite": ("glm47_flash_train", 295.9),
}


def _recorded(name: str):
    with open(os.path.join(spec.BENCH_DIR, "testdata", name)) as fh:
        held = json.load(fh)
    names = held["names"]
    planes = tr.planes_from_json([{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [[names[i], s, d] for i, s, d in ln["events"]]}
        for ln in p["lines"]]} for p in held["planes"]])
    return held, tr.device_planes(planes)[0]


def _observed(held, chip, cell_name, monkeypatch, tmp_path):
    cell = spec.resolve_cell(cell_name, trace=True)
    obs = Observed(
        cell=cell, devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        end_to_end={}, window_s=1.0, attempted=0, failed=0, checks={},
        compile_stats={}, memory_peak_bytes=0, work=dict(held["work"]),
        step_program=held["step_program"], trace_dir=str(tmp_path))
    obs.__dict__["trace"] = TraceView([chip], tr.traced_window([chip]), None)
    monkeypatch.setattr(compilecache, "scope_map", lambda label: held["modules"])
    return obs


def _read(name: str, obs):
    return spec.load_module("layer_metrics", name).read(obs)


# --------------------------------------------- the split, on the older excerpts


@pytest.mark.parametrize("family", sorted(OLDER))
def test_the_three_passes_and_unscoped_add_up_to_each_execution(family):
    held, chip = _recorded(f"v5e_{family}_step_scopes.json")
    split = passes.split_by_pass([chip], held["step_program"], held["modules"], scopes)
    assert len(split.executions) == len(tr.program_runs(chip, held["step_program"])) - 2
    for parts, total in zip(split.executions, split.durations_ns):
        assert sum(parts.values()) == pytest.approx(total)
        by_pass = {which: sum(ns for key, ns in parts.items()
                              if passes.pass_key(key)[1] == which)
                   for which in scopes.PASSES}
        assert all(by_pass.values())
        assert sum(by_pass.values()) + parts[passes.UNSCOPED] == pytest.approx(total)
    # the same executions, the same unscoped time, as the two-pass split's
    older = hs.split_plane(chip, held["step_program"], held["modules"], scopes)
    assert split.durations_ns == older.durations_ns
    assert split.unscoped_share() == pytest.approx(older.unscoped_share())
    # what is computed again is a part of what the older readers call backward
    model = (scopes.MODEL, scopes.LOSS)
    backward = older.median_ms(hs.families(scopes)["backward"])
    forward = older.median_ms(hs.families(scopes)["forward"])
    select = passes._wanted
    assert split.median_ms(select(model, ("recompute", "backward"))) == pytest.approx(backward)
    assert split.median_ms(select(model, "forward")) == pytest.approx(forward)
    assert 0 < split.median_ms(select(model, "recompute")) < backward
    assert split.median_ms(select(None, "recompute")) == pytest.approx(
        OLDER[family][1], abs=0.06)
    # nothing outside the model is computed again, and no projection has the scope yet
    assert split.median_ms(select((scopes.OPTIMIZER, scopes.BATCH_GATHER), "recompute")) == 0
    assert split.median_ms(select(scopes.MIXER_PROJ, None)) == 0


def test_pass_key_and_the_view_of_the_table():
    assert passes.pass_key("faa_model/faa_gqa/faa_mixer_proj/recompute") == (
        ("faa_model", "faa_gqa", "faa_mixer_proj"), "recompute")
    assert passes.pass_key(passes.UNSCOPED) == ((), None)
    view = passes._ByPass(scopes)
    again = ("jit(multi_fn)/transpose(jvp(faa_model))/x/jvp(faa_model)/x/checkpoint/"
             "rematted_computation/layer2/faa_short_conv/conv/faa_mixer_proj/in_proj/dot_general")
    assert view.scope_of(again) == ("faa_model", "faa_model", "faa_short_conv",
                                    "faa_mixer_proj", "recompute")
    assert hs.scope_key(again, view) == "faa_model/faa_short_conv/faa_mixer_proj/recompute"
    assert view.scope_of("jit(multi_fn)/convert_element_type") == ()
    assert hs.scope_key("jit(multi_fn)/faa_optimizer/add", view) == "faa_optimizer/forward"


def test_the_printer_prints_every_scope_by_pass(tmp_path):
    held, chip = _recorded("v5e_lfm2_moe_step_scopes.json")
    split = passes.split_by_pass([chip], held["step_program"], held["modules"], scopes)
    lines = passes.format_table("train_dispatch (^jit_multi_fn)", split, scopes)
    assert "2 executions" in lines[0] and "531.3" in lines[0]
    assert lines[1].split() == ["forward", "recompute", "backward", "all"]
    rows = {ln.split()[0]: ln.split()[1:] for ln in lines[2:] if ln.startswith("    faa_")
            and len(ln.split()) == 5}
    assert {scopes.MODEL, scopes.SHORT_CONV, scopes.SHORT_CONV_GATE, scopes.GQA,
            scopes.GQA_ATTENTION, scopes.MOE, scopes.OPTIMIZER} <= set(rows)
    forward, again, backward, whole = map(float, rows[scopes.MODEL])
    assert again == pytest.approx(55.576, abs=1e-3)
    assert forward + again + backward == pytest.approx(whole, abs=0.01)
    # the cores' forward kernel runs once a step (PR 47): nothing of it computed again
    assert float(rows[scopes.GQA_ATTENTION][1]) == 0.0
    with pytest.raises(SystemExit, match="--trace 1"):
        passes.table_from_files(str(tmp_path))


# ----------------------------------------------- the projections, by hand


def test_projections_of_one_gqa_and_one_short_convolution_layer_by_hand():
    tokens = 16384
    lfm2 = spec.resolve_cell("lfm2_8b_a1b_train").config["model"]
    one = dict(lfm2, layer_types=["conv"], layers_held=1)
    conv = projections.held_products("lfm2_moe", one)
    assert conv == [(2048, 6144), (2048, 2048)]
    assert projections.operations(conv, tokens, backward=False) == (
        2 * tokens * 2048 * 6144 + 2 * tokens * 2048 * 2048)
    assert projections.operations(conv, tokens, backward=True) == 2 * (
        2 * tokens * 2048 * 6144 + 2 * tokens * 2048 * 2048)
    # forward: x, W read and y written; backward: dy, x, W read and dx, dW written
    assert projections.moved_bytes(conv, tokens, backward=False) == 4 * (
        (tokens * 2048 + 2048 * 6144 + tokens * 6144)
        + (tokens * 2048 + 2048 * 2048 + tokens * 2048))
    assert projections.moved_bytes(conv, tokens, backward=True) == 4 * (
        (tokens * 6144 + 2 * tokens * 2048 + 2 * 2048 * 6144)
        + (tokens * 2048 + 2 * tokens * 2048 + 2 * 2048 * 2048))
    # an ungated grouped-query mixer: 32 heads of 64 on 8 key-value heads
    one = dict(lfm2, layer_types=["full_attention"], layers_held=1)
    gqa = projections.held_products("lfm2_moe", one)
    assert gqa == [(2048, 2048), (2048, 512), (2048, 512), (2048, 2048)]
    # Trinity's is gated, window and full alike: 32 heads of 128 on 4 key-value heads
    trinity = spec.resolve_cell("trinity_mini_train").config["model"]
    gated = projections.held_products("afmoe", dict(trinity, layers_held=1))
    assert gated == [(2048, 4096), (2048, 512), (2048, 512), (2048, 4096), (4096, 2048)]
    whole = projections.held_products("afmoe", trinity)
    assert whole == gated * 6
    owed = sum(projections.operations(whole, tokens, backward=b) for b in (False, True))
    assert owed / 197e12 * 1e3 == pytest.approx(81.6, abs=0.05)   # ISSUE 51's figure


def test_projections_by_family_over_the_layers_held():
    lfm2 = spec.resolve_cell("lfm2_8b_a1b_train").config["model"]
    held = projections.held_products("lfm2_moe", lfm2)
    kinds = lfm2["layer_types"][:lfm2["layers_held"]]
    assert len(held) == 2 * kinds.count("conv") + 4 * kinds.count("full_attention") == 18
    nemotron = spec.resolve_cell("nemotron3_nano_30b_a3b_train").config["model"]
    held = projections.held_products("nemotron_h", nemotron)
    pattern = nemotron["pattern"][:nemotron["layers_held"]]
    assert pattern == "MEMEM*EME"
    # Mamba-2: hidden -> z, x, B, C and a step a head; an expert layer has no mixer
    assert held.count((2688, 2 * 4096 + 2 * 1024 + 64)) == held.count((4096, 2688)) - 1 == 4
    assert len(held) == 2 * pattern.count("M") + 4 * pattern.count("*")
    assert (2688, 256) in held and (2688, 4096) in held
    # latent attention and KDA have no table: their readers give None
    for family in ("kimi_linear", "glm4_moe_lite", "wideresnet"):
        assert projections.held_products(family, {}) is None


# --------------------------------------------------- the entries and the readers


def test_each_of_the_three_entries_lists_exactly_the_three_cells():
    bench = spec.load_benchmark()
    for name in NEW_READERS:
        [entry] = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == list(CELLS)
        assert entry["layer"] == "models" and entry["source"] == "device_trace"
        assert entry["moves"] == "train_images_per_s"
        meta = spec.load_module("layer_metrics", name).META
        assert all(meta[key] == entry[key] for key in ("unit", "source", "layer", "moves"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["mixer_proj_roofline"]["unit"] == "%"
    assert by_name["mixer_proj_roofline"]["better"] == "higher"
    assert {by_name[n]["unit"] for n in NEW_READERS[:2]} == {"ms"}
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(NEW_READERS)


@pytest.mark.parametrize("family", ["lfm2_moe", "nemotron_h", "afmoe"])
def test_readers_on_a_step_from_before_the_scope(family, monkeypatch, tmp_path):
    """The older excerpts' programs: the pass is read (``pass_of`` needs
    nothing of the program but its paths), the projections' scope is not
    there, so its time reads 0 and the share has nothing to divide by."""
    held, chip = _recorded(f"v5e_{family}_step_scopes.json")
    obs = _observed(held, chip, OLDER[family][0], monkeypatch, tmp_path)
    assert _read("model_recompute_device_ms", obs) == pytest.approx(OLDER[family][1], abs=0.06)
    assert _read("model_recompute_device_ms", obs) <= _read("model_backward_device_ms", obs)
    assert _read("mixer_proj_device_ms", obs) == 0.0
    assert _read("mixer_proj_roofline", obs) is None
    assert os.path.isfile(hs.map_path(str(tmp_path), obs.cell.traffic["dispatch_label"]))


def test_readers_on_the_parents_program(monkeypatch, tmp_path):
    """This tree's benchmark files over a program from before PR 51: no
    ``pass_of``, no ``MIXER_PROJ``; each new reader returns None and does
    not raise, untraced too."""
    held, chip = _recorded("v5e_lfm2_moe_step_scopes.json")
    obs = _observed(held, chip, "lfm2_8b_a1b_train", monkeypatch, tmp_path)
    for name in ("pass_of", "PASSES", "MIXER_PROJ"):
        monkeypatch.delattr(scopes, name)
    for name in NEW_READERS:
        assert _read(name, obs) is None, name
    assert passes.program_passes() is None and passes.pass_split(obs) is None
    untraced = types.SimpleNamespace(
        cell=obs.cell, work={}, devices=obs.devices, trace=None, trace_dir=None,
        step_program=obs.step_program)
    monkeypatch.undo()
    for name in NEW_READERS:
        assert _read(name, untraced) is None, name


# ------------------------------ the three readers on PR 51's own recorded step


@pytest.fixture(scope="module")
def own():
    return _recorded("v5e_lfm2_moe_step_passes.json")


def test_the_three_readers_on_the_recorded_step_of_this_pr(own, monkeypatch, tmp_path):
    held, chip = own
    obs = _observed(held, chip, "lfm2_8b_a1b_train", monkeypatch, tmp_path)
    values = {name: _read(name, obs) for name in held["expected"]}
    for name, expected in held["expected"].items():
        assert values[name] == pytest.approx(expected, rel=1e-6), name
    assert set(NEW_READERS) <= set(held["expected"])
    again, products, share = (values[name] for name in NEW_READERS)
    # what is computed again is a part of the backward pass's time, and a tenth of the step
    assert 0 < again < values["model_backward_device_ms"]
    assert 0.08 < again / values["step_device_ms"] < 0.13
    # the projections lie inside the mixers' scopes, which the older readers sum
    assert 0 < products < values["short_conv_device_ms"] + values["gqa_device_ms"]
    assert products == pytest.approx(hs.scope_ms(obs, scopes.MIXER_PROJ))
    by_pass = [passes.scope_pass_ms(obs, scopes.MIXER_PROJ, which) for which in scopes.PASSES]
    assert all(ms > 0 for ms in by_pass) and sum(by_pass) == pytest.approx(products, rel=1e-3)
    assert passes.scope_pass_ms(obs, scopes.MIXER_PROJ, ("forward", "backward")) == (
        pytest.approx(by_pass[0] + by_pass[2], rel=1e-3))
    # the share by hand: the operations bound it; five convolution mixers and two ungated
    # grouped-query ones of 32 heads of 64 on 8 key-value heads, three passes' worth of one
    conv = 2048 * 6144 + 2048 * 2048
    gqa = 2 * 2048 * 2048 + 2 * 2048 * 512
    operations = 3 * 2 * 16384 * (5 * conv + 2 * gqa)
    assert share == pytest.approx(100 * (operations / 197e12) / (products / 1e3), rel=1e-6)
    assert 0 < share < 100
    # a mixer less its core less its projections: what is neither kernel nor product
    rest = (values["gqa_device_ms"] - hs.scope_ms(obs, scopes.GQA_ATTENTION)
            + values["short_conv_device_ms"] - hs.scope_ms(obs, scopes.SHORT_CONV_GATE)
            - products)
    assert 0 < rest < 0.1 * products


def test_the_recorded_step_holds_the_scope_only_inside_a_mixer(own):
    held, chip = own
    split = passes.split_by_pass([chip], held["step_program"], held["modules"], scopes)
    chains = {passes.pass_key(k)[0] for parts in split.executions for k in parts}
    under = [chain for chain in chains if scopes.MIXER_PROJ in chain]
    assert under and all(chain[-1] == scopes.MIXER_PROJ for chain in under)
    assert {chain[-2] for chain in under} == {scopes.GQA, scopes.SHORT_CONV}
    for parts, total in zip(split.executions, split.durations_ns):
        assert sum(parts.values()) == pytest.approx(total)
    assert split.unscoped_share() <= 5.0
