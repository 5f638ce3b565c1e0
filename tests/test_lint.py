"""repro.lint — the JAX-aware static analyzer (DESIGN.md §16).

Three layers of coverage:

  * every tier-1 pass fires on its committed known-bad fixture under
    ``tests/lint_fixtures/`` and fires *only* its own rule;
  * the tier-2 jaxpr walks fire on traced fixture functions, and the
    recompilation detector provably catches an injected
    knob-into-program-structure mutation of a real campaign trial;
  * HEAD is clean: the tier-1 analyzer reports nothing on the tree
    (the full tier-2 baseline diff runs in `make lint` / CI, not here —
    it traces all ~70 campaign programs)."""

import json
import shutil
from pathlib import Path

import jax
import pytest

from repro.lint import ast_passes, cli, jaxpr_passes
from repro.lint.allowlist import Allowlist
from repro.lint.report import Violation, render

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = ROOT / "tests" / "lint_fixtures"

TIER1_FIXTURES = {
    "fx_traced_branch.py": "traced-branch",
    "fx_host_cast.py": "host-cast",
    "fx_np_in_trace.py": "np-in-trace",
    "fx_host_callback_bad.py": "np-in-trace",
    "fx_key_reuse.py": "key-reuse",
    "fx_knob_literal.py": "knob-literal",
    "fx_obs_key.py": "obs-key",
}


def _run_tier1_passes(mod):
    knobs = ast_passes.knob_names(ROOT)
    registered = ast_passes.registered_obs_keys(ROOT)
    out = []
    out.extend(ast_passes.check_trace_bodies(mod))
    out.extend(ast_passes.check_key_reuse(mod))
    out.extend(ast_passes.check_knob_literals(mod, knobs))
    out.extend(ast_passes.check_obs_keys(mod, registered))
    return out


@pytest.mark.parametrize("fixture,rule", sorted(TIER1_FIXTURES.items()))
def test_fixture_triggers_exactly_its_rule(fixture, rule):
    mod = ast_passes.load_modules(ROOT, [FIXTURES / fixture])[0]
    violations = _run_tier1_passes(mod)
    assert violations, f"{fixture} must trigger {rule}"
    assert {v.rule for v in violations} == {rule}


def test_host_callback_bodies_are_exempt():
    """np / float() inside a function handed to io_callback /
    jax.debug.callback is host-side work, not a trace violation."""
    mod = ast_passes.load_modules(
        ROOT, [FIXTURES / "fx_host_callback_good.py"])[0]
    assert _run_tier1_passes(mod) == []


def test_tap_surface_is_lint_registered():
    """The obs-key closure covers the tap surface: every TAP key is
    parsed from schema.py, and trainer.py's `payload` writes are
    checked against it (HEAD-clean test would catch an unregistered
    key; here we check the registry side directly)."""
    registered = ast_passes.registered_obs_keys(ROOT)
    from repro.obs import schema
    assert registered["tap"] == set(schema.TAP)
    assert "step" in registered["tap"]


def test_fixture_report_format_is_file_line():
    mod = ast_passes.load_modules(
        ROOT, [FIXTURES / "fx_key_reuse.py"])[0]
    line = render(ast_passes.check_key_reuse(mod)).splitlines()[0]
    # precise file:line:col prefix, then the rule id
    assert line.startswith("tests/lint_fixtures/fx_key_reuse.py:8:")
    assert " key-reuse " in line


def test_scenario_hash_fixture(tmp_path):
    fake = tmp_path / "src" / "repro" / "campaign"
    fake.mkdir(parents=True)
    shutil.copy(FIXTURES / "fx_scenario_field.py", fake / "scenario.py")
    violations = ast_passes.check_scenario_hash(
        tmp_path, FIXTURES / "scenario_fields_baseline.json")
    assert [v.rule for v in violations] == ["scenario-hash"]
    assert "new_knob" in violations[0].message


def test_scenario_hash_declaration_matches_head():
    baseline = json.loads(cli.SCENARIO_BASELINE.read_text())["fields"]
    assert baseline == ast_passes.scenario_fields(ROOT)


def test_head_is_clean_tier1():
    allow = Allowlist.load(ROOT)
    kept, _ = allow.filter(cli.run_tier1(ROOT))
    kept.extend(allow.stale_entries())
    assert not kept, "\n" + render(kept)


def test_nested_lambda_violation_reported_once(tmp_path):
    """Nested bodies are pruned from the enclosing walk: a .item()
    inside a lambda inside a jitted fn is one violation, not two."""
    bad = tmp_path / "fx.py"
    bad.write_text(
        "import jax\n\n\n"
        "def make_step():\n"
        "    def step_fn(x):\n"
        "        f = lambda y: y.item()\n"
        "        return f(x)\n"
        "    return jax.jit(step_fn)\n")
    mod = ast_passes.load_modules(tmp_path, [bad])[0]
    violations = ast_passes.check_trace_bodies(mod)
    assert [v.rule for v in violations] == ["host-cast"]


def test_closure_taint_reaches_nested_lambda(tmp_path):
    """An enclosing trace body's param stays tainted inside a nested
    lambda (pruning must not lose closure-captured tracers)."""
    bad = tmp_path / "fx.py"
    bad.write_text(
        "import jax\n\n\n"
        "def make_step():\n"
        "    def step_fn(x):\n"
        "        f = lambda y: float(x) + y\n"
        "        return f(0.0)\n"
        "    return jax.jit(step_fn)\n")
    mod = ast_passes.load_modules(tmp_path, [bad])[0]
    violations = ast_passes.check_trace_bodies(mod)
    assert [v.rule for v in violations] == ["host-cast"]
    assert "float" in violations[0].message


def test_inline_allow_suppresses(tmp_path):
    bad = tmp_path / "fx.py"
    bad.write_text(
        "import jax\n\n\n"
        "def make_step():\n"
        "    def step_fn(state, grads):\n"
        "        if grads > 0:  # lint: allow(traced-branch)\n"
        "            state = state + grads\n"
        "        return state\n"
        "    return jax.jit(step_fn)\n")
    mod = ast_passes.load_modules(tmp_path, [bad])[0]
    assert not ast_passes.check_trace_bodies(mod)


def test_allowlist_stale_entry_reported(tmp_path):
    (tmp_path / "lint-allowlist.txt").write_text(
        "key-reuse  src/never/exists.py\n")
    allow = Allowlist.load(tmp_path)
    kept, _ = allow.filter([])
    stale = allow.stale_entries()
    assert kept == [] and len(stale) == 1
    assert stale[0].rule == "stale-allow"


def test_stale_detection_only_on_full_runs(tmp_path):
    """A partial run (CI-style `--tier 2`) must not call a tier-1
    allowlist entry stale — only `--tier all` sees every violation."""
    (tmp_path / "lint-allowlist.txt").write_text(
        "knob-literal  src/repro/core/safeguard.py  threshold_scale\n")
    for tier, expect_stale in (("1", 0), ("2", 0), ("all", 1)):
        allow = Allowlist.load(tmp_path)
        kept, suppressed = cli.apply_allowlist([], allow, tier)
        assert suppressed == []
        assert len(kept) == expect_stale, tier
        if kept:
            assert kept[0].rule == "stale-allow"


# ---------------------------------------------------------------------------
# tier 2
# ---------------------------------------------------------------------------

def _fx_tier2():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fx_tier2", FIXTURES / "fx_tier2.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def test_sqrt_diff_walk_fires_on_fixture():
    m = _fx_tier2()
    bad = jax.make_jaxpr(m.unclamped_dist)(1.0, 2.0)
    good = jax.make_jaxpr(m.clamped_dist)(1.0, 2.0)
    assert [v.rule for v in
            jaxpr_passes.find_unclamped_sqrt(bad, "fx")] == ["sqrt-diff"]
    assert not jaxpr_passes.find_unclamped_sqrt(good, "fx")


def test_f64_walk_fires_on_fixture():
    m = _fx_tier2()
    with jax.enable_x64(True):
        bad = jax.make_jaxpr(m.promotes_f64)(1.0)
    assert [v.rule for v in
            jaxpr_passes.find_f64(bad, "fx")] == ["f64"]
    clean = jax.make_jaxpr(m.clamped_dist)(1.0, 2.0)
    assert not jaxpr_passes.find_f64(clean, "fx")


def test_rng_counts_stable_and_nonempty():
    def draw(key):
        k1, k2 = jax.random.split(key)
        return jax.random.normal(k1, (2,)) + jax.random.uniform(k2, (2,))

    c1 = jaxpr_passes.rng_counts(
        jax.make_jaxpr(draw)(jax.random.PRNGKey(0)))
    c2 = jaxpr_passes.rng_counts(
        jax.make_jaxpr(draw)(jax.random.PRNGKey(0)))
    assert c1 == c2
    assert c1.get("random_split", 0) >= 1
    assert c1.get("random_bits", 0) >= 2


def _smoke_scenario(steps=8):
    from repro.campaign import engine
    from repro.campaign.run import CAMPAIGNS
    scens = [s for s in CAMPAIGNS["smoke"](1, steps)
             if s.defense == "safeguard_double"]
    return engine.group_scenarios(scens)[0][0]


def test_recompilation_detector_catches_injected_knob_leak():
    """Acceptance: bake a knob value into a copy of the trial fn (the
    exact regression class the engine's knobs-as-lanes design forbids)
    and assert the invariance probe flags it."""
    from repro.campaign import engine

    s = _smoke_scenario()

    def leaky_make(scenario):
        trial = engine.make_trial_fn(scenario)
        baked = float(scenario.threshold_floor)   # leaks into structure

        def mutated(knobs):
            k = dict(knobs)
            k["threshold_floor"] = baked
            return trial(k)
        return mutated

    caught = jaxpr_passes.check_knob_invariance(
        s, "mutated-smoke", make_fn=leaky_make,
        knobs=["threshold_floor"])
    assert [v.rule for v in caught] == ["knob-structure"]
    assert "threshold_floor" in caught[0].message


def test_clean_trial_is_knob_invariant():
    s = _smoke_scenario()
    assert not jaxpr_passes.check_knob_invariance(
        s, "clean-smoke", knobs=["threshold_floor", "attack_scale"])


def test_baselines_pinned_for_committed_programs():
    """The committed baseline files cover every current campaign
    program label (regenerating is explicit: --update-baselines) and
    record the jax version they were generated under."""
    hashes_doc = json.loads(jaxpr_passes.JAXPR_BASELINE.read_text())
    rng_doc = json.loads(jaxpr_passes.RNG_BASELINE.read_text())
    assert hashes_doc["jax"] == rng_doc["jax"]
    hashes, rng = hashes_doc["programs"], rng_doc["programs"]
    assert set(hashes) == set(rng)
    assert len(hashes) > 50
    for campaign in jaxpr_passes.CAMPAIGN_NAMES[:4]:
        assert any(lab.startswith(campaign + "/") for lab in hashes), \
            campaign


def test_baseline_version_skew_collapses_to_one_report(tmp_path):
    """Hash diffs under a different jax version are version skew, not a
    repo regression: they collapse to a single 'rerun under jax X'
    violation instead of a per-program avalanche."""
    path = tmp_path / "jaxpr_hashes.json"
    pinned = {"p1": "aaaa", "p2": "bbbb", "p3": "cccc"}
    current = {"p1": "aaaa", "p2": "beef", "p3": "feed"}

    path.write_text(json.dumps({"jax": "0.0.1", "programs": pinned}))
    skewed = jaxpr_passes._diff_baseline(path, current, "jaxpr-drift", "h")
    assert len(skewed) == 1
    assert "jax 0.0.1" in skewed[0].message

    # same diffs under the SAME version: real drift, reported per program
    path.write_text(json.dumps(
        {"jax": jaxpr_passes._jax_version(), "programs": pinned}))
    real = jaxpr_passes._diff_baseline(path, current, "jaxpr-drift", "h")
    assert len(real) == 2
    assert all(v.rule == "jaxpr-drift" for v in real)

    # version skew with NO diffs stays silent (pretty-printing stable)
    path.write_text(json.dumps({"jax": "0.0.1", "programs": current}))
    assert jaxpr_passes._diff_baseline(path, current, "jaxpr-drift", "h") \
        == []


def test_violation_format():
    v = Violation("f64", "src/x.py", 3, "msg", col=7)
    assert v.format() == "src/x.py:3:7: f64 msg"
