"""Distributed semantics: run a small 8-device host-platform mesh in a
subprocess (device count must be fixed before jax initializes, so it can't
run in the main pytest process) and check that the sharded safeguard step
produces bit-identical decisions and numerically identical aggregates to
the single-device run."""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import SafeguardConfig, init_state, safeguard_step
from repro.core import tree_utils as tu

m, d1, d2 = 4, 16, 6
cfg = SafeguardConfig(m=m, T0=5, T1=10, threshold_floor=0.2)
key = jax.random.PRNGKey(0)
params = {"w": jnp.zeros((d1, d2)), "b": jnp.zeros((d2,))}

def grads_at(t):
    k = jax.random.fold_in(key, t)
    g = {"w": 1.0 + 0.05 * jax.random.normal(k, (m, d1, d2)),
         "b": 1.0 + 0.05 * jax.random.normal(jax.random.fold_in(k, 1),
                                             (m, d2))}
    # worker 0 is byzantine: sign flip
    return jax.tree.map(lambda x: x.at[0].set(-x[0]), g)

# ---- single device reference -------------------------------------------
st = init_state(cfg, params)
for t in range(12):
    st, agg_ref, info_ref = safeguard_step(st, grads_at(t), cfg)
good_ref = np.asarray(st.good)

# ---- sharded (data=4 workers, model=2) ----------------------------------
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
gspec = {"w": NamedSharding(mesh, P("data", None, "model")),
         "b": NamedSharding(mesh, P("data", "model"))}
step = jax.jit(lambda s, g: safeguard_step(s, g, cfg))
with mesh:
    st2 = init_state(cfg, params)
    for t in range(12):
        g = jax.tree.map(lambda x, s: jax.device_put(x, s), grads_at(t),
                         gspec)
        st2, agg, info = step(st2, g)
good_shard = np.asarray(st2.good)

assert (good_ref == good_shard).all(), (good_ref, good_shard)
assert not good_ref[0] and good_ref[1:].all()
np.testing.assert_allclose(np.asarray(agg["w"]), np.asarray(agg_ref["w"]),
                           rtol=1e-5, atol=1e-5)

# gram under sharding == gram locally
g = grads_at(99)
gs = jax.tree.map(lambda x, s: jax.device_put(x, s), g, gspec)
with mesh:
    gram_sharded = np.asarray(jax.jit(tu.tree_gram)(gs))
gram_local = np.asarray(tu.tree_gram(g))
np.testing.assert_allclose(gram_sharded, gram_local, rtol=1e-4, atol=1e-4)
print("DISTRIBUTED_OK")
"""


def test_sharded_safeguard_matches_local():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "DISTRIBUTED_OK" in out.stdout, (out.stdout, out.stderr)


_MOE_SCRIPT = r"""
import os, re, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
import repro.configs as C
from repro.launch import train as train_lib

data_n, model_n, m = (int(a) for a in sys.argv[1:4])
cfg = C.get_smoke("granite-moe-3b-a800m")
args = train_lib.parse_args([
    "--arch", "granite-moe-3b-a800m", "--workers", str(m), "--byz", "1",
    "--batch", str(2 * m), "--seq", "32", "--attack", "sign_flip",
    "--defense", "safeguard_double", "--t0", "5", "--t1", "10",
    "--floor", "0.01", "--lr", "0.05"])

def run(mesh):
    tr = train_lib.build_trainer(cfg, args, mesh=mesh)
    loss, good = [], []
    for _ in range(12):
        tr.state, met = tr.step_fn(tr.state, next(tr.data_iter))
        loss.append(np.asarray(met["loss"]))
        good.append(np.asarray(met["good"]))
    return tr, np.array(loss), np.array(good)

_, loss_ref, good_ref = run(None)
mesh = jax.make_mesh((data_n, model_n), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
tr, loss, good = run(mesh)
assert (good == good_ref).all(), (good, good_ref)
assert not good[-1][0] and good[:, 1:].all()
np.testing.assert_allclose(loss, loss_ref, rtol=1e-5)

# the grouped matmuls' per-worker loops, per chip: over the chip's own
# m / data workers, not all m
txt = tr.step_fn.lower(tr.state, next(tr.data_iter)).compile().as_text()
trips = [int(n) for n in re.findall(
    r'op_name="[^"]*/moe/experts/[^"]*while"[^\n]*'
    r'known_trip_count":\{"n":"(\d+)"', txt)]
assert trips and set(trips) == {m // data_n}, trips
print("MOE_MESH_OK")
"""


@pytest.mark.parametrize("data_n,model_n,m", [(4, 1, 8), (2, 2, 4)])
def test_held_expert_step_on_a_mesh_matches_one_device(data_n, model_n, m):
    """Granite-MoE's smoke step through ``build_trainer`` on a
    ``(data, model)`` mesh of 4 host devices against one device: the same
    losses and the same evictions in 12 steps (the sign-flipper evicted,
    no honest worker), and each chip runs the grouped matmuls of its own
    two workers only."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _MOE_SCRIPT, str(data_n),
                          str(model_n), str(m)], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "MOE_MESH_OK" in out.stdout, (out.stdout, out.stderr[-4000:])


@pytest.mark.slow
def test_dryrun_single_pair_end_to_end():
    """Full dry-run driver on the smallest pair (its own process — it
    forces 512 host devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "mamba2-130m", "--shape", "decode_32k", "--out",
         "/tmp/dryrun_test"],
        env=env, capture_output=True, text=True, timeout=1200,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "all dry runs OK" in out.stdout, (out.stdout[-2000:],
                                             out.stderr[-2000:])
