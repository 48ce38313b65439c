"""The per-layer reduction of a traced window (``bench/layers.py``): device
time by the program's named layers, idle time by its host spans."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from bench import devtrace, layers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAMES = (("stimulus", "exchange", "compaction", "delivery", "lif", "ring",
          "probes"), ("slot_owner", "syn_gather", "syn_scatter",
                      "drop_count"))
B = "jit(run)/while/body/closed_call"

# window 100..1100 ns on one chip; 2 calls of 5 steps = 10 steps
TOY = {"devices": {"/device:TPU:0": [
    # delivery/compaction: compaction is the innermost layer
    ["fusion.1", "fusion", 100, 100, f"{B}/delivery/compaction/reduce_or"],
    # a part of delivery, overlapping the next op of the same part
    ["fusion.2", "fusion", 200, 100, f"{B}/delivery/syn_gather/gather"],
    ["fusion.3", "fusion", 250, 100, f"{B}/delivery/syn_gather/gather"],
    # a primitive named like a layer is not a layer
    ["fusion.4", "fusion", 400, 50, f"{B}/lif"],
    # looked through a transform's wrapper
    ["fusion.5", "fusion", 450, 50, f"{B}/vmap(lif)/add"],
    ["fusion.6", "fusion", 500, 50, f"{B}/delivery/slot_owner/cumsum"],
    ["copy.7", "copy", 600, 100, "carry.counts"],
    ["while.8", "while", 100, 1000, "jit(run)/while"],
    ["fusion.9", "fusion", 1050, 100, f"{B}/ring/dynamic_update_slice"]]},
    "host": [["window", 100, 1000], ["call", 100, 500],
             ["repro.dispatch", 650, 50], ["fetch", 600, 200],
             ["call", 800, 300], ["repro.init_carry", 800, 100]]}


def test_layer_of():
    names = NAMES[0] + NAMES[1]
    assert layers.layer_of(f"{B}/delivery/syn_scatter/scatter-add",
                           names) == "syn_scatter"
    assert layers.layer_of(f"{B}/stimulus/jit(_bernoulli)/xor",
                           names) == "stimulus"
    assert layers.layer_of("jit(f)/vmap()/while/body/vmap(ring)/select_n",
                           names) == "ring"
    assert layers.layer_of(f"{B}/probes", names) == "other"
    assert layers.layer_of("", names) == "other"


def test_toy_trace_by_layer():
    r = layers.reduce(TOY, chips=1, steps=10, calls=2, names=NAMES)
    ms = 1e-6 / 10          # one ns over 10 steps, in ms per step
    assert r.layers["compaction"] == pytest.approx(100 * ms)
    # syn_gather's union [200, 350] and slot_owner [500, 550] roll up
    assert r.parts["syn_gather"] == pytest.approx(150 * ms)
    assert r.parts["slot_owner"] == pytest.approx(50 * ms)
    assert r.layers["delivery"] == pytest.approx(200 * ms)
    assert r.layers["other"] == pytest.approx((50 + 100) * ms)
    assert r.layers["lif"] == pytest.approx(50 * ms)
    assert r.layers["ring"] == pytest.approx(50 * ms)     # clipped at 1100
    assert r.layers["exchange"] == 0.0
    assert r.busy == pytest.approx(550 * ms)
    assert r.named_share == pytest.approx(400 / 550)
    assert r.metrics(".trials") == {
        "delivery_ms_per_step.trials": r.layers["delivery"],
        "compaction_ms_per_step.trials": r.layers["compaction"],
        "lif_ms_per_step.trials": r.layers["lif"]}
    # gaps [350, 400] and [550, 600] in the first call, [700, 1050] (mid
    # 875) in the second call's init_carry span, the innermost there
    assert r.idle == pytest.approx({"repro.init_carry": 350 / 2 * 1e-6,
                                    "call": 100 / 2 * 1e-6})


def test_gaps_go_to_program_spans():
    data = {"devices": {"/device:TPU:0": [["f", "fusion", 0, 10, "x/lif/a"],
                                          ["g", "fusion", 90, 10,
                                           "x/lif/a"]]},
            "host": [["window", 0, 100], ["call", 0, 100],
                     ["repro.simulate", 5, 90], ["repro.dispatch", 30, 40]]}
    r = layers.reduce(data, chips=1, steps=1, calls=1, names=NAMES)
    assert r.idle == {"repro.dispatch": pytest.approx(80e-6)}


def test_two_chips_mean():
    data = {"devices": {
        "/device:TPU:0": [["f", "fusion", 0, 40, "x/lif/a"]],
        "/device:TPU:1": [["f", "fusion", 0, 20, "x/lif/a"],
                          ["e", "all-gather", 20, 20, "x/exchange/g"]]},
        "host": [["window", 0, 100], ["call", 0, 100]]}
    r = layers.reduce(data, chips=2, steps=1, calls=1, names=NAMES)
    assert r.layers["lif"] == pytest.approx(30e-6)
    assert r.layers["exchange"] == pytest.approx(10e-6)
    assert r.idle == {"call": pytest.approx(60e-6)}


def test_no_scoped_op_raises():
    data = {"devices": {"/device:TPU:0": [
        ["fusion.1", "fusion", 0, 10, "jit(run)/while/body/add"],
        ["fusion.2", "fusion", 10, 10, ""]]},
        "host": [["window", 0, 20]]}
    with pytest.raises(ValueError, match="no operation under a layer"):
        layers.reduce(data, chips=1, steps=1, calls=1, names=NAMES)


def test_program_without_scopes_reduces_to_nothing(monkeypatch):
    monkeypatch.setattr(layers, "scope_names", lambda: None)
    assert layers.reduce(TOY, chips=1, steps=10, calls=2) is None


def test_scope_names_come_from_the_program():
    from repro.obs import scopes
    assert layers.scope_names() == (scopes.LAYERS, scopes.DELIVERY_PARTS)


def test_cut_keeps_what_overlaps():
    c = layers.cut(TOY, 420, 610)
    assert c["host"][0] == ["window", 420, 190]
    assert [h[0] for h in c["host"][1:]] == ["call", "fetch"]
    assert [op[0] for op in c["devices"]["/device:TPU:0"]] == [
        "fusion.4", "fusion.5", "fusion.6", "copy.7", "while.8"]


def test_old_four_field_trace_reduces_as_before():
    """The reduction the benchmark reads is untouched by the fifth field:
    the toy trace reduces to the same busy time with and without it."""
    four = {"devices": {p: [op[:4] for op in ops]
                        for p, ops in TOY["devices"].items()},
            "host": TOY["host"]}
    r = layers.reduce(TOY, chips=1, steps=10, calls=2, names=NAMES)
    assert r.busy == pytest.approx(
        1e3 * devtrace.reduce(four, chips=1).busy_s / 10)


def test_compile_cache_keyed_with_metadata():
    """The benchmark's set-up, which the traced run and ``bench.layers``
    share, keys the compile cache with each program's metadata (JAX's
    default leaves it out)."""
    code = ("import jax; from bench import harness; "
            "flag = lambda: jax.config.jax_compilation_cache_include_"
            "metadata_in_key; print(flag()); "
            "harness.enable_compile_cache(); print(flag())")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([ROOT, os.path.join(ROOT, "src")])
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == ["False", "True"]


def test_load_keeps_program_spans(tmp_path):
    """A CPU trace: the harness's spans and the program's ``repro.*`` spans
    are kept, other host events are not (a CPU has no TPU plane)."""
    import jax
    from repro import obs
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with obs.span("dispatch"):
                jax.numpy.ones(3).block_until_ready()
        with jax.profiler.TraceAnnotation("unrelated"):
            pass
    data = layers.load(str(tmp_path), {})
    assert data["devices"] == {}
    names = {h[0] for h in data["host"]}
    assert {"window", "repro.dispatch"} <= names
    assert "unrelated" not in names


HLO = """HloModule jit__run, is_scheduled=true

%fused_computation (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %neg.1 = s32[8]{0} negate(%p), metadata={op_name="jit(run)/while/body/closed_call/lif/neg"}
}

%body (arg: (s32[], s32[8])) -> (s32[], s32[8]) {
  %arg = (s32[], s32[8]{0}) parameter(0)
  %gte.1 = s32[8]{0} get-tuple-element(%arg), index=1
  %reduce-window.3 = s32[8]{0} reduce-window(%gte.1), window={size=8}
  %copy.4 = s32[8]{0} copy(%reduce-window.3)
  %fusion.5 = s32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run)/while/body/closed_call/delivery/slot_owner/cumsum"}
  %fusion.6 = s32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run)/while/body/closed_call/lif/neg"}
  %broadcast.7 = s32[8]{0} broadcast(%gte.1), metadata={op_name="jit(run)/while/body/closed_call"}
  %add.8 = s32[] add(%gte.1, %gte.1), metadata={op_name="jit(run)/while/body/add"}
  ROOT %tuple.9 = (s32[], s32[8]{0}) tuple(%add.8, %fusion.5, %fusion.6, %broadcast.7)
}
"""


def test_hlo_op_names():
    """Instructions without a layer take that of the nearest user that
    has one; those whose results feed only the loop carry have none."""
    module, names = layers.hlo_op_names(HLO)
    assert module == "jit__run"
    slot = "jit(run)/while/body/closed_call/delivery/slot_owner/cumsum"
    assert names["fusion.5"] == names["copy.4"] == slot
    assert names["reduce-window.3"] == slot
    assert names["fusion.6"].endswith("/lif/neg")
    # gte.1 feeds reduce-window.3 first: the nearest scoped user is lif's
    # fusion.6 (one step) rather than slot_owner's fusion.5 (three)
    assert layers.layer_of(names["gte.1"], layers.scope_names()[0] +
                           layers.scope_names()[1]) == "lif"
    assert names["add.8"] == "jit(run)/while/body/add"
    assert names["broadcast.7"] == "jit(run)/while/body/closed_call"


SHARDED = """HloModule jit_sharded, is_scheduled=true

%body (arg: (s32[], s32[8])) -> (s32[], s32[8]) {
  %arg = (s32[], s32[8]{0}) parameter(0)
  %gte.1 = s32[8]{0} get-tuple-element(%arg), index=1
  %all-reduce.2 = s32[8]{0:T(1024)S(1)} all-reduce(%gte.1), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add.9
  %fusion.3 = s32[8]{0} fusion(%all-reduce.2), kind=kLoop, calls=%fc, metadata={op_name="jit(sharded)/shard_map/while/body/closed_call/delivery/slot_owner/min"}
  %all-gather-start.4 = (s32[2]{0}, s32[8]{0}) all-gather-start(%gte.1), channel_id=3, metadata={op_name="jit(sharded)/shard_map/while/body/closed_call/exchange/all_gather"}
  %all-gather-done.5 = s32[8]{0} all-gather-done(%all-gather-start.4)
  ROOT %tuple.6 = (s32[], s32[8]{0}) tuple(%gte.1, %fusion.3)
}
"""


def test_hlo_collective_is_the_exchange():
    """A collective with no layer scope of its own (the TPU compiler's
    all-reduce standing in for the exchange's all-gather) is the
    exchange's, not its user's; one with its own scope keeps it."""
    _, names = layers.hlo_op_names(SHARDED)
    every = sum(layers.scope_names(), ())
    assert layers.layer_of(names["all-reduce.2"], every) == "exchange"
    assert layers.layer_of(names["all-gather-done.5"], every) == "exchange"
    assert names["all-gather-start.4"].endswith("/exchange/all_gather")
    assert layers.layer_of(names["fusion.3"], every) == "slot_owner"
    # the operand feeding only the collective takes its layer
    assert layers.layer_of(names["gte.1"], every) == "exchange"


def test_compiled_programs_of_a_call():
    """The HLO of the program a call runs is captured by module name, and
    its scan's instructions carry the layers."""
    from repro.core import SimConfig, simulate, synthetic_flywire
    from repro.exp import build_scenario
    c = synthetic_flywire(n=300, target_synapses=3000, seed=0)
    cfg = SimConfig(engine="event")
    stim = build_scenario("sugar_feeding", c, cfg, n_sugar=10)
    with layers.compiled_programs() as programs:
        simulate(c, cfg, 7, stimulus=stim)
    module, names = layers.hlo_op_names(programs["jit__run_scan_jit"])
    every = sum(layers.scope_names(), ())
    found = {layers.layer_of(o, every) for o in names.values()}
    assert {"compaction", "slot_owner", "syn_gather", "syn_scatter",
            "stimulus", "lif", "ring"} <= found


# after the TPU compiler's v5e HLO of the vmapped trials scan: two scatters
# without metadata, fused; each combiner keeps its layer's scope
VMAPPED = """HloModule jit__run_scan_trials_jit, is_scheduled=true

%region_7.25 (scatter-add.7: s32[], scatter-add.8: s32[]) -> s32[] {
  %scatter-add.8 = s32[] parameter(1), metadata={op_name="scatter-add"}
  %scatter-add.7 = s32[] parameter(0), metadata={op_name="scatter-add"}
  ROOT %add.346 = s32[] add(%scatter-add.7, %scatter-add.8), metadata={op_name="delivery/compaction/add" stack_frame_id=55}
}

%region_13.46 (scatter-add.17: f32[], scatter-add.18: f32[]) -> f32[] {
  %scatter-add.18 = f32[] parameter(1), metadata={op_name="scatter-add"}
  %scatter-add.17 = f32[] parameter(0), metadata={op_name="scatter-add"}
  ROOT %add.375 = f32[] add(%scatter-add.17, %scatter-add.18), metadata={op_name="delivery/while/body/syn_scatter/add" stack_frame_id=91}
}

%fused_computation.14 (param_0.863: s32[2048], param_1.1134: s32[80384], param_2.1064: s32[80384]) -> s32[2048] {
  %param_0.863 = s32[2048]{0} parameter(0)
  %param_1.1134 = s32[80384]{0} parameter(1)
  %reshape.851 = s32[4,20096]{1,0} reshape(%param_1.1134)
  %param_2.1064 = s32[80384]{0} parameter(2)
  %reshape.852 = s32[4,20096]{1,0} reshape(%param_2.1064)
  ROOT %scatter.73 = s32[2048]{0} scatter(%param_0.863, %reshape.851, %reshape.852), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=2, to_apply=%region_7.25
}

%fused_computation.148 (param_0.864: s32[80384], param_1.1135: s32[80384], param_2.1065: s32[2048]) -> s32[2048] {
  %param_2.1065 = s32[2048]{0} parameter(2)
  %param_0.864 = s32[80384]{0} parameter(0)
  %param_1.1135 = s32[80384]{0} parameter(1)
  ROOT %fusion.135 = s32[2048]{0} fusion(%param_2.1065, %param_0.864, %param_1.1135), kind=kCustom, calls=%fused_computation.14
}

%fused_computation.10 (param_0.921: f32[80004], param_1.1175: s32[16384], param_2.1086: f32[16384]) -> f32[80004] {
  %param_0.921 = f32[80004]{0} parameter(0)
  %param_1.1175 = s32[16384]{0} parameter(1)
  %reshape.888 = s32[4,4096]{1,0} reshape(%param_1.1175)
  %param_2.1086 = f32[16384]{0} parameter(2)
  %reshape.889 = f32[4,4096]{1,0} reshape(%param_2.1086), metadata={op_name="jit(_run_scan_trials_jit)/vmap()/while/body/closed_call/delivery/while/body/syn_gather/jit(_where)/select_n"}
  ROOT %scatter.77 = f32[80004]{0} scatter(%param_0.921, %reshape.888, %reshape.889), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=2, to_apply=%region_13.46
}

%body (arg: (s32[], s32[80384], f32[16384], s32[2048])) -> (s32[], s32[2048], f32[80004]) {
  %arg = (s32[], s32[80384], f32[16384], s32[2048]) parameter(0)
  %gte.1 = s32[80384]{0} get-tuple-element(%arg), index=1
  %gte.2 = f32[16384]{0} get-tuple-element(%arg), index=2
  %gte.3 = s32[2048]{0} get-tuple-element(%arg), index=3
  %copy.4 = s32[2048]{0} copy(%gte.3)
  %fusion.145 = s32[2048]{0} fusion(%gte.1, %gte.1, %copy.4), kind=kCustom, calls=%fused_computation.148
  %fusion.160 = s32[16384]{0} fusion(%fusion.145), kind=kLoop, calls=%fc, metadata={op_name="jit(_run_scan_trials_jit)/vmap()/while/body/closed_call/delivery/slot_owner/gather"}
  %fusion.170 = f32[80004]{0} fusion(%gte.2, %fusion.160, %gte.2), kind=kCustom, calls=%fused_computation.10
  ROOT %tuple.9 = (s32[], s32[2048], f32[80004]) tuple(%gte.1, %fusion.145, %fusion.170)
}
"""


def test_hlo_scatter_takes_its_combiners_layer():
    """A fusion without metadata is attributed by what it fuses: the
    vmapped compaction's ``where`` scatter is compaction's though the slot
    loop uses it, the ``segment_sum`` scatter is ``syn_scatter``'s though
    the synapse gather feeds it; an unscoped copy feeding the compaction
    scatter is compaction's too."""
    _, names = layers.hlo_op_names(VMAPPED)
    every = sum(layers.scope_names(), ())
    by_layer = {k: layers.layer_of(v, every) for k, v in names.items()}
    assert by_layer["fusion.145"] == "compaction"
    assert by_layer["copy.4"] == "compaction"
    assert by_layer["fusion.170"] == "syn_scatter"
    assert by_layer["fusion.160"] == "slot_owner"


FIXTURE = os.path.join(HERE, "fixtures", "trace_q19_sugar_layers.json")


def test_recorded_chip_trace_by_layer():
    """A cut of a traced ``q19_sugar`` window recorded on a TPU v5 lite
    (``bench/layers.py --fixture``): a call's start and the steps after
    it."""
    with open(FIXTURE) as f:
        data = json.load(f)
    r = layers.reduce(data, chips=1, steps=1, calls=1)
    # the named layers cover the busy time
    assert r.named_share >= 0.9
    assert r.layers["delivery"] > r.layers["compaction"] > 0
    assert r.parts["syn_gather"] > r.parts["syn_scatter"] > 0
    # idle time inside the call falls in the program's spans
    inside = {k: v for k, v in r.idle.items() if k != "fetch"}
    assert inside and all(k.startswith("repro.") for k in inside)
    assert {"repro.init_carry", "repro.scenario"} <= set(inside)


def _recorded_measure(counters):
    """A ``work.Measure`` of the recorded ``q19_sugar`` cut, as the traced
    run builds it."""
    from bench import harness, work
    with open(FIXTURE) as f:
        data = json.load(f)
    red = devtrace.reduce(data, chips=1)
    lay = layers.reduce(data, chips=1, steps=1, calls=1)
    win = harness.Window(setup_s=0.0, window_s=red.window_s, calls=1,
                         steps=1, lanes=1, dt_ms=0.1, chips=1)
    kind = type("Kind", (), {"device_kind": "TPU v5 lite"})
    spec = {"neuron_bytes": 34, "neuron_ops": 14, "event_bytes": 16,
            "event_ops": 1}
    return lay, work.Measure.of(red, win, spec, 139_255, 0, kind,
                                layers={**lay.layers, **lay.parts},
                                counters=counters)


@pytest.mark.parametrize("metric,layer", [
    ("delivery_ms_per_step", "delivery"),
    ("compaction_ms_per_step", "compaction"),
    ("delivery_ms_per_step.trials", "delivery"),
    ("compaction_ms_per_step.trials", "compaction")])
def test_layer_readers_on_recorded_trace(metric, layer):
    from bench import harness
    reader = harness._load_module(harness.BENCH, "metrics", metric)
    lay, m = _recorded_measure({})
    assert reader.read(m) == lay.layers[layer] > 0
    empty = dataclasses.replace(m, layers={})
    assert reader.read(empty) is None


def test_slot_chunks_reader_on_recorded_trace():
    from bench import harness
    reader = harness._load_module(harness.BENCH, "metrics",
                                  "slot_chunks_per_step")
    _, m = _recorded_measure({"slot_chunks": 0.149})
    assert reader.read(m) == 0.149
    assert reader.read(dataclasses.replace(m, counters={})) is None


def test_traced_run_reports_layers_and_counters(monkeypatch):
    """The traced run's plumbing on the CPU, the recorded chip trace standing
    in for the profiler's: the per-layer metrics of a ``q19_sugar`` run come
    from its layers and its program counters."""
    from bench import harness, work
    from tiny import tiny_cell
    with open(FIXTURE) as f:
        data = json.load(f)
    seen = {}

    def load(trace_dir, programs):
        seen.update(programs)
        return data

    peaks = work.peaks
    monkeypatch.setattr(layers, "load", load)
    monkeypatch.setattr(work, "peaks", lambda kind: peaks("TPU v5 lite"))
    cell = tiny_cell("q19_sugar")
    r = harness.run_cell(cell, 2**31 + 5, 0.01, True, 0.0,
                         require_tpu=False)
    assert r["correct"] is True
    assert {"delivery_ms_per_step", "compaction_ms_per_step",
            "slot_chunks_per_step", "device_ms_per_step", "step_mfu",
            "device_idle_share"} == set(r["metrics"])
    assert r["metrics"]["slot_chunks_per_step"]["unit"] == "chunks"
    assert r["metrics"]["slot_chunks_per_step"]["value"] > 0
    assert 0 < r["metrics"]["compaction_ms_per_step"]["value"]
    assert {"busy_s", "window_s"} <= set(r["device"])
    # the warm-up kept the HLO of the program it ran, outside the window
    assert "jit__run_scan_jit" in seen
