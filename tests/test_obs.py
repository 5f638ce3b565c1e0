"""Flight-recorder subsystem (DESIGN.md §15): typed metric schema,
trace sidecars, event extraction, forensics reports, profiling — plus
the satellite regressions (store `_jsonify` round-trip, `scan_trial`
trace-field validation, `Trainer` vector-metric routing)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign import run as campaign_run
from repro.campaign.engine import run_scenarios
from repro.campaign.scenario import Scenario, scenario_id
from repro.campaign.store import CampaignStore, _jsonify
from repro.configs.base import TrainConfig
from repro.core import attacks as atk_lib
from repro.core import defenses as dfn_lib
from repro.data import tasks
from repro.data.pipeline import worker_split
from repro.obs import (Event, MetricSpec, SchemaError, caught_curve,
                       events_from_json, events_to_json, extract_events,
                       register_metric, replay_good, summarize,
                       validate_info, validate_metrics)
from repro.obs import events as ev_lib
from repro.obs import profile as prof
from repro.obs import report as report_lib
from repro.obs import schema as schema_lib
from repro.obs import trace as trace_lib
from repro.optim import make_optimizer
from repro.train import Trainer, init_train_state, make_train_step, \
    scan_trial

M, NBYZ = 10, 4
BYZ = jnp.arange(M) < NBYZ


# ------------------------------------------------------------- schema


def test_schema_accepts_canonical_step_metrics():
    metrics = {"loss": jnp.zeros(()), "n_good": jnp.zeros(()),
               "caught_byz": jnp.zeros((), jnp.int32),
               "good": jnp.ones((M,), bool),
               "dist_to_med_B": jnp.zeros((M,)),
               "threshold_B": jnp.zeros(())}
    assert validate_metrics(metrics, M) is metrics


def test_schema_rejects_unknown_name():
    with pytest.raises(SchemaError, match="not_a_metric"):
        validate_metrics({"not_a_metric": jnp.zeros(())}, M)


def test_schema_rejects_wrong_shape_class():
    # dist_to_med_B is per_worker: a scalar violates the shape class
    with pytest.raises(SchemaError, match="dist_to_med_B"):
        validate_metrics({"dist_to_med_B": jnp.zeros(())}, M)
    # and a per-worker loss is just as wrong
    with pytest.raises(SchemaError, match="loss"):
        validate_metrics({"loss": jnp.zeros((M,))}, M)


def test_schema_rejects_wrong_dtype_kind():
    with pytest.raises(SchemaError, match="caught_byz"):
        validate_metrics({"caught_byz": jnp.zeros((), jnp.float32)}, M)


def test_schema_dtype_by_kind_not_exact():
    # an at-scale bf16 loss is the same metric (kind: floating)
    validate_metrics({"loss": jnp.zeros((), jnp.bfloat16)}, M)


def test_schema_info_surface_and_per_bucket():
    info = {"good": jnp.ones((M,), bool),
            "n_good": jnp.asarray(float(M)),
            "bucket_good": jnp.ones((M // 2,), bool)}
    assert validate_info(info, M) is info
    with pytest.raises(SchemaError, match="bucket_good"):
        # length must divide m
        validate_info({"bucket_good": jnp.ones((3,), bool)}, M)


@pytest.mark.parametrize("held", [0, 2])
def test_expert_buffer_fill_is_a_per_worker_share(held):
    """``expert_buffer_fill`` is registered per worker, from the model;
    the held-expert load of Granite-MoE's smoke model under a worker
    ``vmap`` carries it, and it lies in (0, 1]: 1 where the layers hold
    every expert and compute all T * K assignments."""
    import dataclasses
    import repro.configs as C
    from repro.models import transformer as T
    spec = schema_lib.spec_of("expert_buffer_fill")
    assert (spec.shape_class, spec.source) == (schema_lib.PER_WORKER,
                                               "model")
    cfg = dataclasses.replace(C.get_smoke("granite-moe-3b-a800m"),
                              n_held_experts=held)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (M, 2, 32), 0,
                                cfg.vocab_size)
    _, load = jax.vmap(lambda t: T.loss_and_load(params, cfg,
                                                 {"tokens": t}))(tokens)
    validate_metrics(load, M)
    fill = np.asarray(load["expert_buffer_fill"])
    assert fill.shape == (M,)
    assert ((fill > 0) & (fill <= 1)).all()
    if not held:
        np.testing.assert_array_equal(fill, 1.0)


def test_register_metric_refuses_silent_redefinition():
    spec = MetricSpec("test_only_metric", "float32", schema_lib.SCALAR,
                      "probe")
    register_metric(spec)
    try:
        with pytest.raises(SchemaError, match="already registered"):
            register_metric(spec)
        register_metric(spec, overwrite=True)      # explicit is fine
    finally:
        del schema_lib.METRICS["test_only_metric"]


# ------------------------------------------------------------- events


def _synthetic_traces(steps=12, m=4):
    """Hand-built dense traces with one eviction, one restoration, a
    re-eviction, an escape firing, and a controller reversal."""
    good = np.ones((steps, m), bool)
    good[3:6, 1] = False           # evicted at 3
    good[6:, 1] = True             # restored at 6
    good[8:, 2] = False            # evicted at 8
    dist = np.full((steps, m), 0.1, np.float32)
    th = np.full((steps,), 1.0, np.float32)
    dist[3, 1] = 1.5               # guard-B trigger for the eviction
    dist[8, 2] = 2.5
    esc = np.zeros((steps,), np.float32)
    esc[5:7] = 1.0                 # one rising edge at 5
    lvl = np.array([1, 2, 3, 4, 3, 2, 3, 4, 5, 6, 6, 6], np.float64)
    return {"good": good, "dist_to_med_B": dist, "threshold_B": th,
            "escape_on": esc, "grad_norm": np.ones((steps,), np.float32),
            "attack_level": lvl, "caught_byz": (~good[:, :2]).sum(1)}


def test_extract_events_taxonomy():
    traces = _synthetic_traces()
    events = extract_events(traces)
    kinds = {}
    for e in events:
        kinds.setdefault(e.kind, []).append(e)
    ev1, ev2 = kinds["eviction"]
    assert (ev1.step, ev1.worker, ev1.guard) == (3, 1, "B")
    assert ev1.value == pytest.approx(1.5) and ev1.threshold == 1.0
    assert (ev2.step, ev2.worker) == (8, 2)
    (res,) = kinds["restoration"]
    assert (res.step, res.worker) == (6, 1)
    assert [(e.step, e.worker) for e in kinds["threshold_crossing"]] == \
        [(3, 1), (8, 2)]
    (esc,) = kinds["escape_fire"]
    assert esc.step == 5 and esc.worker == ev_lib.GLOBAL
    # level ramps 1..4, reverses down at t=4, reverses up again at t=6
    assert [e.step for e in kinds["attack_phase_change"]] == [4, 6]


def test_replay_good_bit_matches():
    traces = _synthetic_traces()
    events = extract_events(traces)
    assert np.array_equal(replay_good(events, 4, 12), traces["good"])


def test_single_guard_mirror_suppressed():
    """safeguard_single publishes guard A as a copy of guard B; the
    extractor must not double-count its events."""
    traces = _synthetic_traces()
    traces["dist_to_med_A"] = traces["dist_to_med_B"].copy()
    traces["threshold_A"] = traces["threshold_B"].copy()
    events = extract_events(traces)
    crossings = [e for e in events if e.kind == "threshold_crossing"]
    assert {e.guard for e in crossings} == {"B"}
    evictions = [e for e in events if e.kind == "eviction"]
    assert all(e.guard == "B" for e in evictions)


def _canon(records):
    return json.dumps(records, sort_keys=True)


def test_events_json_roundtrip_exact():
    events = extract_events(_synthetic_traces())
    back = events_from_json(json.loads(json.dumps(events_to_json(events))))
    # canonical-json compare: NaN fields defeat `==` (nan != nan), but
    # f32 -> f64 widening is lossless so the strings are bit-faithful
    assert _canon(events_to_json(back)) == _canon(events_to_json(events))


def test_summarize_counts():
    traces = _synthetic_traces()
    s = summarize(extract_events(traces), n_byz=2, m=4)
    assert s["caught"][1]["step"] == 3           # worker 1 is byzantine
    assert s["n_caught"] == 1
    assert s["false_evictions"] == {2: 8}        # worker 2 is honest
    assert s["restorations"] == 1
    assert s["attack_phase_changes"] == 2
    assert s["escape_fires"] == 1


# --------------------------------------------- acceptance: engine cell


@pytest.fixture(scope="module")
def variance_cell():
    scn = Scenario(attack="variance", defense="safeguard_double",
                   steps=40)
    rec = run_scenarios([scn])[scenario_id(scn)]
    return scn, rec


def test_variance_cell_events_name_every_colluder(variance_cell):
    """ISSUE 7 acceptance: the event layer names every caught colluder
    with eviction step and triggering guard/threshold, matching the
    trainer's caught_byz trace exactly."""
    scn, rec = variance_cell
    events = events_from_json(rec["events"])
    traces = {k: np.asarray(v) for k, v in rec["traces"].items()}

    # the record's stored events ARE the re-extraction (bit-match)
    assert _canon(rec["events"]) == _canon(
        events_to_json(extract_events(traces)))

    # replay matches the trainer's own timeline bit-for-bit
    assert np.array_equal(replay_good(events, scn.m, scn.steps),
                          traces["good"].astype(bool))
    assert np.array_equal(
        caught_curve(events, scn.n_byz, scn.m, scn.steps),
        traces["caught_byz"])

    s = summarize(events, n_byz=scn.n_byz, m=scn.m)
    final_caught = int(traces["caught_byz"][-1])
    assert final_caught > 0                      # the attack IS detected
    assert s["n_caught"] >= final_caught
    for k, c in s["caught"].items():
        assert k < scn.n_byz
        assert c["guard"] in ("B", "A", "BA")
        assert c["dist"] >= c["threshold"]


def test_eviction_forensics_narrative(variance_cell):
    scn, rec = variance_cell
    traces = {k: np.asarray(v) for k, v in rec["traces"].items()}
    s = summarize(events_from_json(rec["events"]), n_byz=scn.n_byz,
                  m=scn.m)
    worker, info = next(iter(s["caught"].items()))
    text = report_lib.eviction_forensics(traces, worker)
    assert f"worker {worker} evicted at step {info['step']}" in text
    assert "dist_B" in text and "thresh_B" in text
    # an honest, never-evicted worker gets the negative narrative
    text2 = report_lib.eviction_forensics(traces, scn.m - 1)
    assert "never evicted" in text2


# ------------------------------------------- store + report, end to end


@pytest.fixture(scope="module")
def traced_campaign(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("obs_store"))
    out = campaign_run.main(["--campaign", "smoke", "--steps", "25",
                             "--seeds", "1", "--root", root,
                             "--store-traces"])
    assert out["ran"] > 0
    return root


def test_sidecars_written_and_loadable(traced_campaign):
    store = CampaignStore("smoke", root=traced_campaign)
    records = store.load()
    for sid, rec in records.items():
        assert "traces" not in rec["result"]     # not inlined
        traces = store.load_traces(sid)
        assert traces is not None
        assert traces["loss"].shape == (25,)
        assert traces["loss"].dtype == np.float32     # dtype preserved
        assert rec["result"]["trace_fields"] == sorted(traces)


def test_report_check_events_passes(traced_campaign):
    assert report_lib.main(["--campaign", "smoke",
                            "--root", traced_campaign,
                            "--check-events"]) == 0


def test_campaign_report_renders(traced_campaign):
    store = CampaignStore("smoke", root=traced_campaign)
    text = report_lib.campaign_report(store, store.load())
    assert "# obs report" in text
    assert "| cell |" in text


def test_resume_leaves_sidecars_untouched(traced_campaign):
    import glob
    import os
    paths = sorted(glob.glob(os.path.join(traced_campaign, "smoke",
                                          "traces", "*.npz")))
    assert paths
    before = {p: (os.path.getmtime(p), open(p, "rb").read())
              for p in paths}
    out = campaign_run.main(["--campaign", "smoke", "--steps", "25",
                             "--seeds", "1", "--root", traced_campaign,
                             "--store-traces"])
    assert out["ran"] == 0                       # full resume
    for p in paths:
        assert open(p, "rb").read() == before[p][1]


# ------------------------------------------------- _jsonify (satellite)


def test_jsonify_roundtrip_regression():
    payload = {
        "f": np.float32(1.5), "i": np.int64(3), "b": np.bool_(True),
        "jax_scalar": jnp.asarray(2.5),
        "nested": {"arr": np.array([True, False]),
                   "list": [np.float32(0.25), {"deep": jnp.arange(3)}]},
        "nan": float("nan"), "inf": np.float32(np.inf),
        "none": None, "s": "str",
    }
    out = _jsonify(payload)
    back = json.loads(json.dumps(out))
    assert back["f"] == 1.5 and back["i"] == 3 and back["b"] is True
    assert back["jax_scalar"] == 2.5
    assert back["nested"]["arr"] == [True, False]
    assert back["nested"]["list"][1]["deep"] == [0, 1, 2]
    assert np.isnan(back["nan"]) and np.isinf(back["inf"])
    assert back["none"] is None and back["s"] == "str"
    # bool stays bool even though bool < int in the isinstance chain
    assert type(out["b"]) is bool and type(out["i"]) is int


def test_jsonify_loud_on_unknown_type():
    with pytest.raises(TypeError, match=r"\$\.a\[1\]"):
        _jsonify({"a": [1, object()]})


# -------------------------------------- scan_trial + Trainer (satellites)


@pytest.fixture(scope="module")
def tiny_setup():
    task = tasks.make_teacher_task(d_in=8, d_hidden=8, n_classes=4)
    opt = make_optimizer(TrainConfig(lr=0.1))
    defense = dfn_lib.make_registry(M, NBYZ, T0=5, T1=15)[
        "safeguard_double"]
    attack = atk_lib.make_registry()["variance"]
    params = tasks.student_init(task)
    state = init_train_state(params, opt, defense=defense, attack=attack)
    step = make_train_step(tasks.mlp_loss, opt, byz_mask=BYZ,
                           defense=defense, attack=attack, jit=False)

    def batch_fn(t):
        key = jax.random.fold_in(jax.random.PRNGKey(0xDA7A), t)
        return worker_split(tasks.teacher_batch(task, key, 50), M)
    return task, state, step, batch_fn


def test_scan_trial_trace_fields_subset(tiny_setup):
    _, state, step, batch_fn = tiny_setup
    _, traces = scan_trial(step, state, batch_fn=batch_fn, steps=6,
                           trace_fields=("loss", "good"))
    assert sorted(traces) == ["good", "loss"]
    assert traces["loss"].shape == (6,)
    assert traces["good"].shape == (6, M)


def test_scan_trial_unknown_field_named_error(tiny_setup):
    _, state, step, batch_fn = tiny_setup
    with pytest.raises(ValueError, match="unknown trace field.*typo_xyz"):
        scan_trial(step, state, batch_fn=batch_fn, steps=6,
                   trace_fields=("loss", "typo_xyz"))


def test_scan_trial_empty_trace_fields_drops_memory(tiny_setup):
    _, state, step, batch_fn = tiny_setup
    final, traces = scan_trial(step, state, batch_fn=batch_fn, steps=6,
                               trace_fields=())
    assert traces == {}
    assert int(final.step) == 6                  # trial still ran


def test_trainer_routes_vector_metrics(tiny_setup, capsys):
    task, state, step, _ = tiny_setup
    it = tasks.teacher_batches(task, 50, m=M)
    tr = Trainer(state, jax.jit(step), it, log_every=10 ** 9, name="obs")
    tr.run(4, verbose=True)
    out = capsys.readouterr().out
    assert "routed to .traces" in out
    assert out.count("routed to .traces") == 1   # surfaced once per run
    # history holds scalars only; vectors landed in traces
    assert all(np.ndim(v) == 0 for rec in tr.history
               for v in rec.values())
    arrs = tr.trace_arrays()
    assert arrs["good"].shape == (4, M)
    assert arrs["dist_to_med_B"].shape == (4, M)
    # the routed traces feed the event layer directly
    extract_events(arrs)


# ----------------------------------------------------------- profiling


def test_phase_timer_disjoint_nesting():
    pt = prof.PhaseTimer()
    with pt.phase("outer"):
        with pt.phase("inner"):
            pass
    s = pt.summary()
    assert set(pt.seconds) == {"outer", "inner"}
    assert s["total_s"] >= 0
    assert abs(s["outer_frac"] + s["inner_frac"] - 1.0) < 1e-3


def test_profile_compiled_reports_phases():
    def f(x):
        return (x * 2.0).sum()

    rec = prof.profile_compiled(f, jnp.ones((8, 8)), repeats=2,
                                analyze=False)
    assert rec["compile_s"] > 0 and rec["execute_s"] > 0
    assert float(rec["_out"]) == 128.0
    assert "_out" not in prof.strip_private(rec)


# ------------------------------------------------------- trace module


def test_save_load_traces_roundtrip(tmp_path):
    traces = {"a": np.arange(6, dtype=np.float32).reshape(3, 2),
              "b": np.array([True, False, True])}
    rel = trace_lib.save_traces(str(tmp_path), "sid123", traces)
    assert rel == trace_lib.trace_relpath("sid123")
    back = trace_lib.load_trace_file(
        trace_lib.trace_path(str(tmp_path), "sid123"))
    for k in traces:
        assert back[k].dtype == traces[k].dtype
        np.testing.assert_array_equal(back[k], traces[k])


def test_load_cell_traces_missing_sidecar_is_loud(tmp_path):
    rec = {"id": "x", "result": {"trace_file": "traces/x.npz"}}
    with pytest.raises(FileNotFoundError, match="x.npz"):
        trace_lib.load_cell_traces(str(tmp_path), rec)
