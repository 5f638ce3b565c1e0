"""Live telemetry — layer 4 of the flight recorder (DESIGN.md §17).

The load-bearing invariant: tapping is OBSERVATION ONLY.  A tapped
trial consumes the same rng stream and produces the same accuracy as
the untapped one, on every campaign program family (iid, hetero,
saddle); integer/boolean traces are bit-identical everywhere.  Float
traces are bit-identical on the programs tested here except where XLA
re-fuses shared subexpressions across the nested-scan boundary — those
stay within 1 ULP and are locked with a tight allclose (the caveat is
documented in DESIGN.md §17).

Also covered: the LiveCollector host side (ring, heartbeat files,
step_rate, lane mapping, never-raise), the alert-rule catalog on
synthetic streams (each rule fires exactly on its trigger and never on
a clean stream), and the regression gate's offline comparison path."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign import engine
from repro.campaign.run import CAMPAIGNS
from repro.obs import alerts as alerts_lib
from repro.obs import live as live_lib
from repro.obs import schema as obs_schema

STEPS = 40
TAP_EVERY = 10


class _Sink:
    """Bare-list tap target (the io_callback side of LiveCollector)."""

    def __init__(self):
        self.payloads = []

    def __call__(self, payload):
        self.payloads.append({k: np.asarray(v) for k, v in
                              payload.items()})


def _first_group(campaign, lanes=2):
    scenarios = CAMPAIGNS[campaign](1, STEPS)
    return engine.group_scenarios(scenarios)[0][:lanes]


# ------------------------------------------------ tapped == untapped


@pytest.mark.parametrize("campaign", ["live", "hetero", "saddle"])
def test_tapped_trial_is_untapped_trial(campaign):
    group = _first_group(campaign)
    base = engine.run_group(group)
    sink = _Sink()
    tapped = engine.run_group(group, tap=sink, tap_every=TAP_EVERY)

    assert len(sink.payloads) == (STEPS // TAP_EVERY) * len(group)
    for lane, (b, t) in enumerate(zip(base, tapped)):
        # the acceptance invariant: accuracy diff is exactly 0.0
        assert float(b["acc"]) == float(t["acc"]), f"lane {lane}"
        for key in ("caught_byz", "evicted_honest"):
            if key in b:
                assert int(b[key]) == int(t[key])
        assert set(b["traces"]) == set(t["traces"])
        for k in sorted(b["traces"]):
            a0 = np.asarray(b["traces"][k])
            a1 = np.asarray(t["traces"][k])
            if a0.dtype.kind in "ib":
                np.testing.assert_array_equal(a0, a1, err_msg=k)
            else:
                # float traces: exact up to XLA's nested-scan re-fusion
                # (<= 1 ULP on the affected programs — DESIGN.md §17)
                np.testing.assert_allclose(a0, a1, rtol=3e-7, atol=1e-30,
                                           err_msg=k)


def test_tap_payloads_are_schema_typed_with_lane_identity():
    group = _first_group("live")
    sink = _Sink()
    engine.run_group(group, tap=sink, tap_every=TAP_EVERY)
    lanes_seen = set()
    for p in sink.payloads:
        assert set(p) <= set(obs_schema.TAP)
        for k, v in p.items():
            assert v.dtype == np.dtype(obs_schema.TAP[k].dtype), k
            assert v.ndim == 0, f"{k} must arrive unbatched"
        lanes_seen.add(int(p["lane"]))
    assert lanes_seen == set(range(len(group)))
    steps = sorted({int(p["step"]) for p in sink.payloads})
    assert steps == list(range(TAP_EVERY, STEPS + 1, TAP_EVERY))


def test_tapped_rng_signature_is_unchanged():
    """The tap consumes zero rng: primitive-level rng counts of the
    tapped jaxpr equal the untapped one (the lint tier-2 signature)."""
    from repro.lint import jaxpr_passes
    rep = _first_group("live", lanes=1)[0]
    knobs = {k: v[0] for k, v in engine.stack_knobs([rep]).items()}
    plain = jax.make_jaxpr(engine.make_trial_fn(rep))(knobs)
    tapped = jax.make_jaxpr(
        engine.make_trial_fn(rep, tap=lambda p: None,
                             tap_every=TAP_EVERY))(knobs)
    assert jaxpr_passes.rng_counts(plain) == jaxpr_passes.rng_counts(
        tapped)
    assert jaxpr_passes.rng_counts(plain)          # non-trivial program


def test_untapped_program_structure_is_byte_identical():
    """tap_every=0 must be the pre-live-layer program, byte for byte
    (committed tier-2 jaxpr baselines depend on it)."""
    from repro.lint import jaxpr_passes
    rep = _first_group("live", lanes=1)[0]
    knobs = {k: v[0] for k, v in engine.stack_knobs([rep]).items()}
    a = jax.make_jaxpr(engine.make_trial_fn(rep))(knobs)
    b = jax.make_jaxpr(engine.make_trial_fn(rep, tap=lambda p: None,
                                            tap_every=0))(knobs)
    assert str(a) == str(b)


# ------------------------------------------------ scan_trial plumbing


def test_scan_trial_tap_validation():
    from repro.train import scan_trial

    def step(st, batch):
        return st + 1, {"loss": jnp.float32(batch)}

    with pytest.raises(ValueError, match="needs a host `tap`"):
        scan_trial(step, jnp.int32(0), batch_fn=lambda t: t, steps=40,
                   tap_every=10)
    with pytest.raises(ValueError, match="multiple of"):
        scan_trial(step, jnp.int32(0), batch_fn=lambda t: t, steps=40,
                   tap_every=7, tap=lambda p: None)


def test_fit_tap_every_snaps_to_divisor():
    assert engine.fit_tap_every(40, 50) == 40
    assert engine.fit_tap_every(40, 16) == 10
    assert engine.fit_tap_every(40, 10) == 10
    assert engine.fit_tap_every(41, 10) == 1
    assert engine.fit_tap_every(40, 0) == 0
    assert engine.fit_tap_every(40, 1) == 1


def test_validate_tap_rejects_unknown_key():
    with pytest.raises(obs_schema.SchemaError, match="not_a_tap_key"):
        obs_schema.validate_tap({"step": jnp.int32(1),
                                 "not_a_tap_key": jnp.float32(0)})


# ------------------------------------------------ LiveCollector host side


def _beat(step, **kw):
    b = {"step": step, "loss": 1.0, "lane": 0}
    b.update(kw)
    return b


def test_collector_rings_files_and_rates(tmp_path):
    ticks = iter(np.arange(0.0, 100.0, 0.5))
    col = live_lib.LiveCollector(
        name="t", lane_ids=["cellA", "cellB"],
        heartbeat_dir=tmp_path, maxlen=3, clock=lambda: next(ticks))
    # t0 consumed one tick; each tap consumes the next (0.5s apart)
    col.tap({"step": np.int32(10), "loss": np.float32(1.0),
             "lane": np.int32(0)})
    col.tap({"step": np.int32(10), "loss": np.float32(2.0),
             "lane": np.int32(1)})
    col.tap({"step": np.int32(20), "loss": np.float32(0.5),
             "lane": np.int32(0)})
    col.close()
    assert col.dropped == 0
    a = col.beats("cellA")
    assert [b["step"] for b in a] == [10, 20]
    assert a[0].get("step_rate") is None       # no previous beat yet
    # 10 steps in 2 ticks of 0.5s => 10/s
    assert a[1]["step_rate"] == pytest.approx(10.0)
    # files: one JSONL per cell, sorted keys, typed scalars
    streams = live_lib.load_heartbeats(tmp_path)
    assert sorted(streams) == ["cellA", "cellB"]
    assert [b["loss"] for b in streams["cellA"]] == [1.0, 0.5]
    line = (tmp_path / "cellA.jsonl").read_text().splitlines()[0]
    assert json.loads(line)["cell"] == "cellA"
    assert isinstance(json.loads(line)["step"], int)


def test_collector_ring_is_bounded_and_never_raises(tmp_path):
    col = live_lib.LiveCollector(name="solo", maxlen=4)
    for i in range(10):
        col.tap({"step": np.int32(i), "loss": np.float32(i)})
    assert len(col.beats()) == 4                     # ring bounded
    assert [b["step"] for b in col.beats()] == [6, 7, 8, 9]
    assert all(b["cell"] == "solo" for b in col.beats())
    # a poisoned payload is dropped, not raised into the device program
    col.tap({"step": "not-a-number"})
    assert col.dropped == 1
    col.tap({"step": np.int32(10), "loss": np.float32(0)})
    assert [b["step"] for b in col.beats()][-1] == 10


def test_collector_set_lanes_and_unknown_lane():
    col = live_lib.LiveCollector(name="c", lane_ids=["x"])
    col.tap({"step": np.int32(1), "lane": np.int32(5)})
    assert col.beats()[0]["cell"] == "lane5"         # out of range
    col.set_lanes(["p", "q"])
    col.tap({"step": np.int32(1), "lane": np.int32(1)})
    assert col.beats()[-1]["cell"] == "q"


def test_collector_appends_on_resume(tmp_path):
    """Reopening a collector over the same heartbeat dir appends; it
    never truncates (campaign --resume leaves finished cells' files
    byte-identical because skipped cells emit no beats)."""
    with live_lib.LiveCollector(name="r", lane_ids=["c"],
                                heartbeat_dir=tmp_path) as col:
        col.tap({"step": np.int32(1), "lane": np.int32(0)})
    first = (tmp_path / "c.jsonl").read_bytes()
    # resumed run, cell already complete: no beats for it => untouched
    with live_lib.LiveCollector(name="r", lane_ids=["c"],
                                heartbeat_dir=tmp_path):
        pass
    assert (tmp_path / "c.jsonl").read_bytes() == first
    # resumed run with new beats: strictly appended
    with live_lib.LiveCollector(name="r", lane_ids=["c"],
                                heartbeat_dir=tmp_path) as col:
        col.tap({"step": np.int32(2), "lane": np.int32(0)})
    data = (tmp_path / "c.jsonl").read_bytes()
    assert data.startswith(first) and len(data) > len(first)


# ------------------------------------------------ Trainer parity


@pytest.fixture(scope="module")
def trainer_setup():
    from repro.configs.base import TrainConfig
    from repro.core import attacks as atk_lib
    from repro.core import defenses as dfn_lib
    from repro.data import tasks
    from repro.optim import make_optimizer
    from repro.train import init_train_state, make_train_step

    m, nbyz = 6, 2
    byz = jnp.arange(m) < nbyz
    task = tasks.make_teacher_task(d_in=8, d_hidden=8, n_classes=4)
    opt = make_optimizer(TrainConfig(lr=0.1))
    defense = dfn_lib.make_registry(m, nbyz, T0=5, T1=15)[
        "safeguard_double"]
    attack = atk_lib.make_registry()["variance"]

    def fresh():
        params = tasks.student_init(task)
        state = init_train_state(params, opt, defense=defense,
                                 attack=attack)
        step = make_train_step(tasks.mlp_loss, opt, byz_mask=byz,
                               defense=defense, attack=attack, jit=False)
        it = tasks.teacher_batches(task, 48, m=m)
        return state, jax.jit(step), it

    return fresh


def test_trainer_history_identical_with_collector(trainer_setup):
    """The collector observes the log boundary; scalar history is
    bit-identical with and without it."""
    from repro.train import Trainer

    state, step, it = trainer_setup()
    plain = Trainer(state, step, it, log_every=2, name="p")
    h0 = plain.run(6, verbose=False)

    state, step, it = trainer_setup()
    col = live_lib.LiveCollector(name="w")
    watched = Trainer(state, step, it, log_every=2, name="w",
                      collector=col)
    h1 = watched.run(6, verbose=False)

    assert len(h0) == len(h1) == 3
    for r0, r1 in zip(h0, h1):
        assert set(r0) == set(r1)
        for k in r0:
            if k == "wall_s":
                continue                    # host wall-clock, not data
            assert r0[k] == r1[k], k
    beats = col.beats()
    assert [b["step"] for b in beats] == [r["step"] for r in h1]
    assert all(set(b) - {"cell", "t_wall", "step_rate"}
               <= set(obs_schema.TAP) for b in beats)


# ------------------------------------------------ alert rules


def _clean_stream(n=8):
    return [{"step": 10 * (i + 1), "loss": 1.0 - 0.05 * i,
             "honest_loss": 1.0 - 0.05 * i, "n_good": 10.0,
             "caught_byz": 0, "evicted_honest": 0,
             "threshold_B": 1.0 + 0.01 * i, "threshold_A": 2.0,
             "escape_on": 0.0, "min_eig_proxy": 0.1,
             "step_rate": 100.0, "cell": "clean"}
            for i in range(n)]


def test_clean_stream_raises_no_alerts():
    assert alerts_lib.extract_alerts(_clean_stream(), cell="clean") == []


def test_nan_guard_fires_on_first_nonfinite_beat():
    beats = _clean_stream()
    beats[3]["loss"] = float("nan")
    beats[5]["threshold_B"] = float("inf")
    out = alerts_lib.extract_alerts(beats, cell="c")
    nan = [a for a in out if a.rule == "nan_guard"]
    assert len(nan) == 1                        # first poison only
    assert nan[0].severity == alerts_lib.CRITICAL
    assert nan[0].step == beats[3]["step"]
    assert "loss" in nan[0].message


def test_eviction_storm_counts_pre_heartbeat_evictions():
    beats = _clean_stream()
    for b in beats:                              # storm before beat 1
        b["caught_byz"], b["n_good"] = 3, 7.0
    out = [a for a in alerts_lib.extract_alerts(beats, cell="c")
           if a.rule == "eviction_storm"]
    assert len(out) == 1 and out[0].step == beats[0]["step"]


def test_eviction_storm_gradual_eviction_is_quiet():
    beats = _clean_stream()
    for b in beats[4:]:                          # one slow eviction
        b["caught_byz"], b["n_good"] = 1, 9.0
    assert [a.rule for a in alerts_lib.extract_alerts(beats, cell="c")
            ] == []


def test_eviction_storm_rearms_after_restore():
    beats = _clean_stream(12)
    for b in beats[2:5]:                         # first storm
        b["caught_byz"], b["n_good"] = 2, 8.0
    for b in beats[5:8]:                         # periodic reset restores
        b["caught_byz"], b["n_good"] = 0, 10.0
    for b in beats[8:]:                          # second storm
        b["caught_byz"], b["n_good"] = 2, 8.0
    storms = [a for a in alerts_lib.extract_alerts(beats, cell="c")
              if a.rule == "eviction_storm"]
    assert [a.step for a in storms] == [beats[2]["step"],
                                        beats[8]["step"]]


def test_threshold_runaway_fires_once_per_guard():
    beats = _clean_stream(10)
    for b in beats[5:]:
        b["threshold_B"] = 200.0                 # 50x the ~1.0 median
    out = [a for a in alerts_lib.extract_alerts(beats, cell="c")
           if a.rule == "threshold_runaway"]
    assert len(out) == 1
    assert out[0].step == beats[5]["step"]
    assert "threshold_B" in out[0].message


def test_stalled_escape_needs_persistent_negative_curvature():
    beats = _clean_stream(10)
    for b in beats[2:]:
        b["escape_on"], b["min_eig_proxy"] = 1.0, -0.05
    out = [a for a in alerts_lib.extract_alerts(beats, cell="c")
           if a.rule == "stalled_escape"]
    assert len(out) == 1
    assert out[0].step == beats[4]["step"]       # 3rd consecutive beat
    # a single blip does not fire
    beats = _clean_stream(10)
    beats[3]["escape_on"], beats[3]["min_eig_proxy"] = 1.0, -0.05
    assert not [a for a in alerts_lib.extract_alerts(beats, cell="c")
                if a.rule == "stalled_escape"]


def test_step_rate_collapse_fires_and_rearms():
    beats = _clean_stream(10)
    beats[5]["step_rate"] = 10.0                 # < 25% of median 100
    out = [a for a in alerts_lib.extract_alerts(beats, cell="c")
           if a.rule == "step_rate_collapse"]
    assert len(out) == 1 and out[0].step == beats[5]["step"]
    # rule disarms until the rate recovers: a sustained collapse is one
    # alert, a second independent collapse is a second alert
    beats[6]["step_rate"] = 9.0
    beats[8]["step_rate"] = 8.0                  # recovered at 7, re-fires
    out = [a for a in alerts_lib.extract_alerts(beats, cell="c")
           if a.rule == "step_rate_collapse"]
    assert [a.step for a in out] == [beats[5]["step"], beats[8]["step"]]


def test_rules_disarm_without_their_keys():
    """A program that taps only loss arms nothing but nan_guard."""
    beats = [{"step": 10 * i, "loss": 1.0} for i in range(8)]
    assert alerts_lib.extract_alerts(beats, cell="c") == []


# ------------------------------------------------ regression gate


def test_regress_offline_pass_and_fail(tmp_path):
    from benchmarks import regress

    base = {"claim_holds": True, "taps_fired_ok": True,
            "tap50_overhead_frac": 0.001, "tap10_overhead_frac": 0.01}
    (tmp_path / "base").mkdir()
    (tmp_path / "fresh").mkdir()
    suite = regress.SUITES["live"]
    with open(tmp_path / "base" / suite.baseline, "w") as f:
        json.dump(base, f)
    with open(tmp_path / "fresh" / suite.baseline, "w") as f:
        json.dump(base, f)
    assert regress.run(only=["live"], against=str(tmp_path / "fresh"),
                       baseline_dir=tmp_path / "base") == []

    bad = dict(base, claim_holds=False, tap50_overhead_frac=0.5)
    with open(tmp_path / "fresh" / suite.baseline, "w") as f:
        json.dump(bad, f)
    failures = regress.run(only=["live"],
                           against=str(tmp_path / "fresh"),
                           baseline_dir=tmp_path / "base")
    assert len(failures) == 2
    assert any("claim_holds" in f for f in failures)
    assert any("tap50_overhead_frac" in f for f in failures)


def test_regress_committed_baselines_are_self_consistent():
    """The committed BENCH files must pass their own gate (the --check
    path re-measures; here we verify the committed trajectory itself
    honors every ceiling/floor/bool)."""
    from benchmarks import regress

    root = Path(regress.REPO_ROOT)
    for name, suite in regress.SUITES.items():
        with open(root / suite.baseline) as f:
            base = json.load(f)
        assert regress.compare(base, base, suite.checks, name=name) == []


# ------------------------------------------------ CLI gate


def test_alerts_cli_expectations(tmp_path):
    live = tmp_path / "camp" / "live"
    live.mkdir(parents=True)
    clean = _clean_stream()
    stormy = _clean_stream()
    for b in stormy:
        b["caught_byz"], b["n_good"] = 4, 6.0
    for cell, beats in (("none-safeguard", clean),
                        ("variance-safeguard", stormy)):
        with open(live / f"{cell}.jsonl", "w") as f:
            for b in beats:
                f.write(json.dumps(dict(b, cell=cell)) + "\n")
    argv = ["alerts", "--root", str(tmp_path), "--campaign", "camp"]
    assert live_lib.main(argv + ["--expect-clean", "none-",
                                 "--expect",
                                 "eviction_storm:variance-"]) == 0
    assert live_lib.main(argv + ["--expect-clean", "variance-"]) == 1
    assert live_lib.main(argv + ["--expect",
                                 "nan_guard:variance-"]) == 1
    assert live_lib.main(argv + ["--expect",
                                 "eviction_storm:nonexistent"]) == 1


def test_tail_once_renders_latest_beats(tmp_path, capsys):
    live = tmp_path / "camp" / "live"
    live.mkdir(parents=True)
    with open(live / "cellZ.jsonl", "w") as f:
        for b in _clean_stream(3):
            f.write(json.dumps(dict(b, cell="cellZ")) + "\n")
    assert live_lib.main(["tail", "--root", str(tmp_path),
                          "--campaign", "camp", "--once"]) == 0
    out = capsys.readouterr().out
    assert "[cellZ]" in out and "step     30" in out
