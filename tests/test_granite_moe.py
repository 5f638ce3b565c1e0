"""Granite-MoE against its plain reference (``chipbench/reference/
granitemoe.py``), and the safeguard's decisions on its routed experts'
gradients, at the smoke size on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from chipbench import bench
from repro.launch import train as train_lib
from repro.models import transformer as T

ref = bench.reference("granitemoe")

# f32 on both sides on the CPU: they differ only in the order of sums
# (dense attention per head vs per key/value group, sorted grouped matmuls
# vs the dense sum over held experts), which moves the loss by under 1e-7
# of itself and a leaf's gradient by under 1e-6 of its norm (4.4e-7 read)
LOSS_TOL, GRAD_TOL = 1e-6, 1e-5

# each multiplier of the published model, and the value that leaves it out
LEFT_OUT = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "attention_multiplier": 32 ** -0.5, "logits_scaling": 1.0}


def _smoke(**kw):
    return dataclasses.replace(C.get_smoke("granite-moe-3b-a800m"), **kw)


def _model(cfg) -> dict:
    """The reference's configuration of ``cfg``, under the published
    names."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.d_expert,
            "num_experts_routed": cfg.n_experts,
            "num_local_experts": len(cfg.held_experts),
            "first_expert_held": cfg.held_experts.start,
            "num_experts_per_tok": cfg.top_k, "vocab_size": cfg.vocab_size,
            "embedding_multiplier": cfg.embed_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attn_scale,
            "logits_scaling": cfg.logits_divisor,
            "router_aux_loss_coef": cfg.router_aux_coef,
            "rms_norm_eps": 1e-6, "rope_theta": cfg.rope_theta,
            "torch_dtype": "float32"}


def _tokens(cfg, seed=1, L=32):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, L), 0,
                              cfg.vocab_size)


def _by_path(tree) -> dict:
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def _gaps(cfg, model, key):
    """The loss's relative gap and, per leaf, the gradient's gap over the
    leaf's norm, program against reference on the same seeded weights."""
    toks = _tokens(cfg)
    params = T.init_params(cfg, key)
    lp, gp = jax.value_and_grad(
        lambda p: T.loss_fn(p, cfg, {"tokens": toks}))(params)
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(
            lambda p: ref.loss(p, toks, model))(ref.init(model, key))
    gp, gr = _by_path(gp), _by_path(gr)
    grads = {k: float(jnp.linalg.norm(gp[k] - gr[k])
                      / jnp.maximum(jnp.linalg.norm(gr[k]), 1e-30))
             for k in gr if k in gp}
    return abs(float(lp) - float(lr)) / abs(float(lr)), grads, gp, gr


def test_benchmark_configuration_is_the_programs():
    """The benchmark cell's configuration file states, under the published
    names, what its program overrides build: every shape, multiplier and
    the held share the reference is given."""
    cell = bench.load_json(bench.HERE / "configs" /
                           "granite-moe-3b-a800m.json")
    prog = cell["program"]
    cfg = dataclasses.replace(C.get(prog["arch"]), **prog["overrides"])
    want = {**_model(cfg), "torch_dtype": "bfloat16"}
    assert {k: cell["model"][k] for k in want} == want
    assert cfg.param_count() == cell["param_count"]
    assert set(cell["reduced"]) == {"num_hidden_layers", "num_local_experts",
                                    "vocab_size"}


def test_reference_draws_the_programs_weights():
    cfg = _smoke()
    key = jax.random.PRNGKey(3)
    a = _by_path(T.init_params(cfg, key))
    b = _by_path(ref.init(_model(cfg), key))
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


@pytest.mark.parametrize("held", [None, (2, 2)])
def test_loss_and_grads_match_reference(held):
    """The whole layer and a share of two experts, within the tolerances
    above."""
    kw = {} if held is None else {"first_held_expert": held[0],
                                  "n_held_experts": held[1]}
    cfg = _smoke(**kw)
    loss_gap, grads, _, _ = _gaps(cfg, _model(cfg), jax.random.PRNGKey(3))
    assert loss_gap < LOSS_TOL
    assert max(grads.values()) < GRAD_TOL, grads


@pytest.mark.parametrize("name", sorted(LEFT_OUT))
def test_reference_misses_a_left_out_multiplier(name):
    """The comparison above fails when the reference leaves out any one
    of the four multipliers: some leaf's gradient is off by more than
    100 times the tolerance (read: 0.80 to 0.94 of its norm)."""
    cfg = _smoke()
    model = {**_model(cfg), name: LEFT_OUT[name]}
    loss_gap, grads, _, _ = _gaps(cfg, model, jax.random.PRNGKey(3))
    assert max(grads.values()) > 100 * GRAD_TOL


def test_untied_head_misses_the_reference():
    """The published model ties its head to the embedding: an untied
    program has a head of its own, which the reference does not."""
    cfg = _smoke()
    assert cfg.tie_embeddings and C.get("granite-moe-3b-a800m").tie_embeddings
    untied = _smoke(tie_embeddings=False)
    loss_gap, _, gp, gr = _gaps(untied, _model(cfg), jax.random.PRNGKey(3))
    assert "['lm_head']" in gp and "['lm_head']" not in gr
    assert loss_gap > 100 * LOSS_TOL


def _trainer(attack: str, seed: int = 0):
    args = train_lib.parse_args([
        "--arch", "granite-moe-3b-a800m", "--workers", "4",
        "--byz", "1" if attack != "none" else "0", "--batch", "8",
        "--seq", "32", "--attack", attack, "--defense", "safeguard_double",
        "--t0", "5", "--t1", "10", "--floor", "0.01", "--lr", "0.05",
        "--log-every", "20", "--seed", str(seed)])
    return train_lib.build_trainer(_smoke(), args)


@pytest.mark.parametrize("attack", ["none", "sign_flip"])
def test_safeguard_decisions_on_expert_gradients(attack):
    """Through ``build_trainer`` -> ``make_train_step`` (m = 4, the
    one-chip safeguard backend): with no attack no worker is evicted in 20
    steps; with worker 0 flipping its gradient's sign it is evicted and no
    honest worker is.  The step reports each worker's held-expert load."""
    trainer = _trainer(attack)
    seen = []
    for _ in range(20):
        trainer.state, metrics = trainer.step_fn(trainer.state,
                                                 next(trainer.data_iter))
        seen.append(np.asarray(metrics["good"]))
    good = seen[-1]
    if attack == "none":
        assert all(g.all() for g in seen)
    else:
        assert not good[0] and good[1:].all()
        assert all(g[1:].all() for g in seen)
    rows_max = np.asarray(metrics["expert_rows_max"])
    rows_mean = np.asarray(metrics["expert_rows_mean"])
    # 2 x 32 tokens per worker, top 2 of 4 experts: 32 rows per expert on
    # average, at most all 64 tokens
    assert rows_max.shape == rows_mean.shape == (4,)
    np.testing.assert_allclose(rows_mean, 32.0)
    assert (rows_max >= 32).all() and (rows_max <= 64).all()
