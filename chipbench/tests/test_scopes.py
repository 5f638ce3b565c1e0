"""The per-phase reduction (``chipbench/scopes.py``): on synthetic planes
whose answer is known, on a small HLO text, on the small trace of
``record_trace.py`` (whose reduction it leaves as it was), and on a
scoped smoke safeguard step recorded on a TPU v5e by
``record_scoped_trace.py`` and committed beside this file."""

from collections import namedtuple
from pathlib import Path

import pytest

from chipbench import bench, scopes, trace_reduce

DATA = Path(__file__).with_name("data")
Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns")

FWD = "jit(step_fn)/fwd_bwd/vmap(jvp())/while/body/closed_call"
SCOPES = {
    "while.3": "jit(step_fn)/fwd_bwd/vmap(jvp())/while",
    "fusion.1": f"{FWD}/mixer/dot_general",
    "fusion.2": f"{FWD}/mixer/ssd/closed_call/mul",
    "pairwise_sqdist_kernel.4": ("jit(step_fn)/defense/distance/"
                                 "jit(pairwise_sqdist_kernel)/"
                                 "pairwise_sqdist_kernel"),
    "copy.5": "",
    "fusion.6": "jit(step_fn)/optimizer/add",
    "tanh.7": "jit(other)/tanh",
    "add.8": "jit(step_fn)/telemetry/add",
}


def _span(name, start, end):
    return Event(name, start, end - start)


def _op(name, start, end):
    return Event(f"%{name} = f32[4]{{0}} op(...)", start, end - start)


def _planes():
    host = Plane("/host:CPU", [Line("python", [
        _span("chipbench.window", 100, 1850),
        _span("repro.step", 100, 1150),
        _span("repro.batch", 100, 180),
        _span("repro.dispatch", 180, 940),
        _span("chipbench.dispatch", 180, 190),
        _span("chipbench.block", 190, 940),
        _span("repro.log", 940, 1130),
        _span("repro.step", 1150, 1850),
        _span("repro.batch", 1150, 1160),
        _span("repro.dispatch", 1160, 1850),
        _span("chipbench.dispatch", 1160, 1210),
        _span("other", 0, 5000)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [_span("jit_step_fn(1)", 200, 1000),
                             _span("jit_other(2)", 1000, 1100),
                             _span("jit_step_fn(1)", 1200, 2000)]),
        Line("XLA Ops", [
            _op("while.3", 200, 700),
            _op("fusion.1", 250, 350),            # nested in the while
            _op("fusion.2", 400, 600),
            _op("pairwise_sqdist_kernel.4", 700, 800),
            _op("copy.5", 800, 850),              # in the module, no scope
            _op("mystery.9", 850, 900),           # not in the map
            _op("tanh.7", 1000, 1100),            # another module
            _op("fusion.6", 1200, 1300),
            _op("add.8", 1300, 1400),
            _op("while.3", 1400, 1900),           # clipped at 1850
            _op("fusion.1", 1450, 1550)])])
    return [host, dev]


def test_phases_from_synthetic_planes():
    red = scopes.reduce_planes(_planes(), SCOPES, "jit_step_fn", {})
    ns = lambda v: pytest.approx(v * 1e-9)
    # the while keeps its loop control: 500 - 300, then 450 - 100
    assert red["phase_s"] == {"fwd_bwd": ns(200 + 100 + 200 + 350 + 100),
                              "defense": ns(100), "optimizer": ns(100),
                              "telemetry": ns(100)}
    assert red["scope_s"] == {"fwd_bwd/mixer": ns(400),
                              "fwd_bwd/ssd": ns(200),
                              "defense/distance": ns(100)}
    assert red["unscoped_s"] == ns(50)
    assert red["unmapped"] == {"mystery.9": ns(50)}
    # the other module's tanh counts in busy_s, not in the step's
    assert red["step_busy_s"] == ns(700 + 650)
    assert red["busy_s"] == ns(700 + 100 + 650)
    assert (sum(red["phase_s"].values()) + red["unscoped_s"]
            + sum(red["unmapped"].values())) == ns(1350)
    # gaps [100, 200), [900, 1000), [1100, 1200): innermost span over most
    names = {round(at * 1e9): name for name, _, at in red["gaps"]}
    assert names == {0: "repro.batch", 800: "repro.log",
                     1000: "chipbench.dispatch"}


def test_layer_metrics_and_breakdown():
    red = scopes.reduce_planes(_planes(), SCOPES, "jit_step_fn", {})
    out = scopes.layer_metrics(red, steps=2)
    assert out["fwd_bwd_ms"] == pytest.approx(950e-6 / 2)
    assert out["mixer_ms"] == pytest.approx(400e-6 / 2)
    assert out["attack_ms"] is None and out["sg_accumulate_ms"] is None
    assert out["unscoped_share"] == pytest.approx(100 * 50 / 1350)
    ops = dict(scopes.breakdown(red, SCOPES)["device_ops"])
    # same times as trace_reduce's, the names carry phase and scope
    assert ops["while.3 fwd_bwd"] == pytest.approx(950e-9)
    assert ops["pairwise_sqdist_kernel.4 defense/…/distance"] == (
        pytest.approx(100e-9))
    assert ops["mystery.9"] == pytest.approx(50e-9)


def test_scoped_reduction_keeps_the_unscoped_keys():
    red = scopes.reduce_planes(_planes(), SCOPES, "jit_step_fn", {})
    plain = trace_reduce.reduce_planes(_planes())
    for k, v in plain.items():
        if k != "gaps":
            assert red[k] == v, k
    # the gaps keep their times and places; only their names are finer
    assert [g[1:] for g in red["gaps"]] == [g[1:] for g in plain["gaps"]]


HLO = """HloModule jit_step_fn, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(step_fn)/fwd_bwd/while/body/mixer/mul" source_file="x.py" source_line=3}
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%fusion.1)
}

%fc (q: f32[4]) -> (f32[4], f32[4]) {
  %q = f32[4]{0} parameter(0)
  %add.7 = f32[4]{0} add(%q, %q), metadata={op_name="jit(step_fn)/defense/accumulate/add"}
  ROOT %tuple.8 = (f32[4]{0}, f32[4]{0}) tuple(%add.7, %add.7)
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.3 = (s32[], f32[4]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/fwd_bwd/vmap(jvp(embed))/while"}
  %pairwise_sqdist_kernel.4 = f32[1,4,4]{2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", backend_config="{\\"op_name=\\"x\\"}", metadata={op_name="jit(step_fn)/defense/distance/jit(pairwise_sqdist_kernel)/pairwise_sqdist_kernel"}
  %add_select_fusion.6 = (f32[4]{0}, f32[4]{0}) fusion(%a), kind=kLoop, calls=%fc
  %copy.10 = f32[1,4,4]{1,2,0} copy(%pairwise_sqdist_kernel.4)
  ROOT %copy.5 = f32[4]{0} copy(%a)
}
"""

ACC = "jit(step_fn)/defense/accumulate/add"
KERNEL = ("jit(step_fn)/defense/distance/jit(pairwise_sqdist_kernel)/"
          "pairwise_sqdist_kernel")


def test_op_scopes_from_hlo_text():
    got = scopes.op_scopes(HLO)
    mixer = "jit(step_fn)/fwd_bwd/while/body/mixer/mul"
    loop = "jit(step_fn)/fwd_bwd/vmap(jvp(embed))/while"
    assert got == {
        "a": "", "copy.5": "",
        # a parameter of a computation: the scope of what runs it
        "p": loop, "q": mixer,
        "fusion.1": mixer, "tuple.2": mixer,
        "add.7": ACC, "tuple.8": ACC,
        "while.3": loop,
        "pairwise_sqdist_kernel.4": KERNEL,
        # a multi-output fusion without metadata: its fused root's
        "add_select_fusion.6": ACC,
        # a layout copy without metadata: what it copies
        "copy.10": KERNEL}
    assert scopes.module_name(HLO) == "jit_step_fn"
    assert scopes.phase_of(got["fusion.1"]) == "fwd_bwd"
    assert scopes.phase_of(got["copy.5"]) is None
    assert scopes.scopes_of(got["pairwise_sqdist_kernel.4"]) == [
        "defense/distance"]
    # a scope at the top of a transformed function sits inside its name
    assert scopes.scopes_of(got["while.3"]) == ["fwd_bwd/embed"]
    # the compiler hoists model constants out of the phases
    assert scopes.phase_of("jit(step_fn)/mixer/ssd/jit(tril)/ge") == (
        "fwd_bwd")


# trace_reduce's reduction of small.xplane.pb and the readers' values on
# it, as they were before the scoped reduction existed
SMALL = {
    "window_s": 0.021187349, "busy_s": 0.00012575, "devices": 1,
    "ops": {"pairwise_sqdist_kernel.1": 8.3763e-05, "reduce_sum.0": 1.25e-07,
            "or_bitcast_fusion": 4.7e-08, "fusion": 1.733e-06,
            "subtract_maximum_fusion": 1.633e-06,
            "tanh_reduce_fusion": 3.8449e-05},
    "n_gaps": 27,
    "first_gaps": [("chipbench.batch", 0.004238383, 0.014882835),
                   ("chipbench.batch", 0.004198145, 0.010531212),
                   ("chipbench.batch", 0.004133159, 0.00625674)],
}
READERS = {"host_ms_per_step": 8.0, "idle_share": 99.40648544563079,
           "mfu": 0.11979181850404445, "gram_kernel_ms": 0.0167526,
           "gram_roofline": 61.13976702422675}


@pytest.mark.parametrize("scoped", [False, True])
def test_small_trace_reads_as_before(scoped):
    from jax.profiler import ProfileData
    planes = ProfileData.from_file(str(DATA / "small.xplane.pb")).planes
    red = (scopes.reduce_planes(planes, {}, "jit_pairwise_sqdist", {})
           if scoped else trace_reduce.reduce_planes(planes))
    for k in ("window_s", "busy_s", "devices", "ops"):
        assert red[k] == pytest.approx(SMALL[k], rel=1e-12), k
    assert len(red["gaps"]) == SMALL["n_gaps"]
    for got, want in zip(red["gaps"], SMALL["first_gaps"]):
        assert got == (want[0], pytest.approx(want[1], rel=1e-12),
                       pytest.approx(want[2], rel=1e-12))
    ctx = {"trace": red, "chips": 1, "peaks": bench.peaks("TPU v5 lite"),
           "host": {"steps": 5, "window_s": 0.05, "blocked_s": 0.01},
           "step_flops": 1e9, "gram_bytes": 8 * 4 * 262144}
    for name, want in READERS.items():
        assert bench.metric_reader(name)(ctx) == pytest.approx(
            want, rel=1e-12), name


@pytest.fixture(scope="module")
def scoped():
    hlo = scopes.read_hlo(DATA / "scoped.hlo.txt.gz")
    return scopes.reduce(DATA / "scoped.xplane.pb", hlo), hlo


def test_attribute_reports_fused_phases():
    got = scopes.attribute(
        {"fusion.1": 2.0, "neg.2": 0.0, "copy.3": 1.0, "gone.4": 0.5},
        {"fusion.1": "jit(step_fn)/telemetry/reduce_sum",
         "neg.2": "jit(step_fn)/attack/neg", "copy.3": ""},
        fused={"fusion.1": ["attack", "optimizer", "telemetry"]})
    # the attack is in the program, but no traced op is rooted in it
    assert got["phase_s"] == {"telemetry": 2.0, "attack": 0.0}
    assert got["carried_s"] == {"attack": 2.0, "optimizer": 2.0}
    assert got["unscoped_s"] == 1.0 and got["unmapped"] == {"gone.4": 0.5}
    fused = scopes.fused_phases(HLO)
    assert fused == {"fusion.1": ["defense"],
                     "add_select_fusion.6": ["defense"]}


def test_recorded_step_has_every_phase(scoped):
    red, _ = scoped
    assert set(red["phase_s"]) == set(scopes.PHASES)
    assert red["unmapped"] == {}
    total = sum(red["phase_s"].values()) + red["unscoped_s"]
    assert total == pytest.approx(red["step_busy_s"], rel=5e-3)
    for key in ("fwd_bwd/mixer", "fwd_bwd/ssd", "defense/accumulate",
                "defense/distance", "defense/filter", "defense/aggregate"):
        assert red["scope_s"].get(key, 0) > 0, key


def test_recorded_step_names_its_kernel_and_gaps(scoped):
    red, hlo = scoped
    ops = scopes.op_scopes(hlo)
    grams = [k for k in red["ops"] if k.startswith("pairwise_sqdist_kernel")]
    assert grams and all("/defense/distance/" in ops[k] for k in grams)
    assert {name for name, _, _ in red["gaps"]} <= {
        "host", "repro.step", "repro.batch", "repro.dispatch", "repro.log",
        "chipbench.batch", "chipbench.dispatch", "chipbench.block"}
