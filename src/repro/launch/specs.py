"""Step builders + abstract input specs for the dry run.

For each (architecture, input shape) pair this module constructs the jit
target and its fully-sharded ShapeDtypeStruct arguments — no device
allocation ever happens (``jax.eval_shape`` end to end):

  * ``train_4k``            -> the full SafeguardSGD training step (per
    worker grads -> filter -> SGD), m = pod*data workers;
  * ``prefill_32k``         -> full-sequence prefill returning the decode
    cache;
  * ``decode_32k/long_500k`` -> one-token ``serve_step`` against a
    preallocated cache.

``variant`` selects the aggregation implementation for §Perf:
  "exact"    — flat-buffer O(m*d) accumulators (f32, DESIGN.md §6), rows
               sharded over the data axes (so the XLA distance pass — the
               Pallas kernels are per-device programs and cannot be
               partitioned);
  "exact16"  — flat accumulators in bf16;
  "stacked"  — paper-faithful stacked-pytree accumulators (the reference
               representation; keeps per-leaf model-axis sharding);
  "sketch"   — CountSketch safeguard state (beyond paper);
  "mean"     — no safeguard (plain data-parallel SGD; the cost floor).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.core import aggregators as agg_lib
from repro.core import defenses as dfn_lib
from repro.core import safeguard as sg
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as sh
from repro.models import layers as layers_lib
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.train import trainer as tr


def make_sg_cfg(m: int, variant: str = "exact") -> Optional[sg.SafeguardConfig]:
    if variant == "mean":
        return None
    kwargs: Dict[str, Any] = dict(m=m, T0=100, T1=600)
    if variant == "exact16":
        kwargs["acc_dtype"] = jnp.bfloat16
    if variant == "stacked":
        kwargs["engine"] = "stacked"
    if variant == "sketch":
        kwargs.update(use_sketch=True, sketch_k=2048, sketch_reps=4)
    return sg.SafeguardConfig(**kwargs)


def build_train(cfg: ModelConfig, shape: InputShape, mesh, *,
                variant: str = "exact"):
    """Returns (step_fn, arg_structs tuple) for jit(...).lower(*structs)."""
    m = mesh_lib.n_workers(mesh)
    assert shape.global_batch % m == 0
    per = shape.global_batch // m
    Lseq = shape.seq_len

    sg_cfg = make_sg_cfg(m, variant)
    opt = make_optimizer(TrainConfig(lr=0.01, optimizer="sgd"))
    loss = functools.partial(_loss, cfg)
    waxes = mesh_lib.worker_axes(mesh)
    spmd = waxes if len(waxes) > 1 else waxes[0]
    if sg_cfg is not None:
        defense = dfn_lib.make_safeguard_defense(sg_cfg)
    else:
        defense = dfn_lib.from_aggregator(
            agg_lib.Aggregator("mean", agg_lib.mean))
    params_a = T.init_abstract(cfg)
    sg_a = (jax.eval_shape(functools.partial(sg.init_state, sg_cfg), params_a)
            if sg_cfg is not None else None)
    acc_sharding = None
    if defense.flat_state:
        # flat (m, d_pad) defense state: worker rows on the data axes,
        # feature columns on model (DESIGN.md §3/§6) — one rule for every
        # flat-buffer defense, not a safeguard special case
        acc_sharding = NamedSharding(
            mesh, sh.flat_acc_pspec(mesh, sg_a.layout.d_padded))
    step = tr.make_train_step(loss, opt, byz_mask=jnp.zeros((m,), bool),
                              defense=defense, spmd_axis_name=spmd,
                              acc_sharding=acc_sharding,
                              # the zeta trace layer (DESIGN.md §13) is
                              # campaign telemetry; keep the at-scale hot
                              # path free of its two O(m d) passes
                              trace_zeta=False, jit=False)

    # ---- abstract state with shardings --------------------------------
    state_a = tr.TrainState(
        params=params_a, opt_state=jax.eval_shape(opt.init, params_a),
        defense_state=sg_a, attack_state=None,
        step=jax.ShapeDtypeStruct((), jnp.int32),
        rng=jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    state_s = jax.tree.map(
        lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shd),
        state_a, train_state_shardings(state_a, mesh))

    batch_a = _abstract_batch(cfg, m, per, Lseq)
    batch_specs = sh.batch_pspecs(batch_a, mesh, m)
    batch_s = sh.with_shardings(batch_a, batch_specs, mesh)
    return step, (state_s, batch_s)


def train_state_shardings(state: tr.TrainState, mesh) -> tr.TrainState:
    """``NamedSharding`` of every leaf of a :class:`TrainState` (arrays or
    ShapeDtypeStructs): parameters and optimizer state by the FSDP x TP
    rules of ``launch.sharding``, the safeguard accumulators with their
    worker rows on the data axes, everything else replicated."""
    named = lambda spec: NamedSharding(mesh, spec)
    rep = lambda x: named(P(*([None] * len(x.shape))))
    pspecs = sh.params_pspecs(state.params, mesh)
    ds = state.defense_state
    if isinstance(ds, sg.SafeguardState):
        ds = _sg_shardings(ds, sh.stacked_grads_pspecs(pspecs, mesh), mesh)
    else:
        ds = jax.tree.map(rep, ds)
    return tr.TrainState(
        params=jax.tree.map(named, pspecs,
                            is_leaf=lambda x: isinstance(x, P)),
        opt_state=jax.tree_util.tree_map_with_path(
            lambda path, leaf: named(sh.param_pspec(path, leaf, mesh)),
            state.opt_state),
        defense_state=ds, attack_state=jax.tree.map(rep, state.attack_state),
        step=rep(state.step), rng=rep(state.rng))


def _sg_shardings(sg_a: sg.SafeguardState, gspecs, mesh) -> sg.SafeguardState:
    named = lambda spec: NamedSharding(mesh, spec)

    def acc(tree):
        if tree is None:
            return None
        if hasattr(tree, "shape"):
            # flat accumulator (m_pad, d_pad): worker rows on the data
            # axes, feature columns on model (DESIGN.md §3/§6); sketch
            # matrix (m, rk): worker rows on the data axes.
            if sg_a.layout is not None:
                return named(sh.flat_acc_pspec(mesh, sg_a.layout.d_padded))
            waxes = mesh_lib.worker_axes(mesh)
            return named(P(waxes if len(waxes) > 1 else waxes[0], None))
        return jax.tree.map(named, gspecs,
                            is_leaf=lambda x: isinstance(x, P))

    rep = lambda s: named(P(*([None] * len(s.shape))))
    return sg.SafeguardState(
        good=rep(sg_a.good), step=rep(sg_a.step),
        A=acc(sg_a.A), B=acc(sg_a.B), evicted_at=rep(sg_a.evicted_at),
        layout=sg_a.layout)


def _loss(cfg, params, batch):
    return T.loss_fn(params, cfg, batch)


def _abstract_batch(cfg: ModelConfig, m: int, per: int, Lseq: int):
    if cfg.embed_stub:
        return {
            "embeds": jax.ShapeDtypeStruct((m, per, Lseq, cfg.d_model),
                                           cfg.dtype),
            "labels": jax.ShapeDtypeStruct((m, per, Lseq), jnp.int32),
        }
    return {"tokens": jax.ShapeDtypeStruct((m, per, Lseq), jnp.int32)}


# --------------------------------------------------------------------------
# Serving
# --------------------------------------------------------------------------

def build_prefill(cfg: ModelConfig, shape: InputShape, mesh):
    B, Lseq = shape.global_batch, shape.seq_len

    def prefill_step(params, inputs):
        return T.prefill(params, cfg, inputs, max_seq=Lseq)

    params_a = T.init_abstract(cfg)
    pspecs = sh.params_pspecs(params_a, mesh)
    params_s = sh.with_shardings(params_a, pspecs, mesh)

    if cfg.embed_stub:
        inp_a = jax.ShapeDtypeStruct((B, Lseq, cfg.d_model), cfg.dtype)
    else:
        inp_a = jax.ShapeDtypeStruct((B, Lseq), jnp.int32)
    inp_spec = sh.batch_pspecs({"embeds" if cfg.embed_stub else "tokens":
                                inp_a}, mesh)
    inp_s = sh.with_shardings({"x": inp_a},
                              {"x": list(inp_spec.values())[0]}, mesh)["x"]
    return prefill_step, (params_s, inp_s)


def build_decode(cfg: ModelConfig, shape: InputShape, mesh):
    B, Lseq = shape.global_batch, shape.seq_len

    def serve_step(params, token, cache):
        return T.decode_step(params, cfg, token, cache)

    params_a = T.init_abstract(cfg)
    pspecs = sh.params_pspecs(params_a, mesh)
    params_s = sh.with_shardings(params_a, pspecs, mesh)

    cache_a = jax.eval_shape(lambda: T.init_cache(cfg, B, Lseq))
    cache_specs = sh.cache_pspecs(cache_a, mesh, B)
    cache_s = sh.with_shardings(cache_a, cache_specs, mesh)

    data_n = mesh_lib.data_size(mesh)
    waxes = mesh_lib.worker_axes(mesh)
    bspec = (waxes if len(waxes) > 1 else waxes[0]) \
        if B % data_n == 0 else None
    if cfg.embed_stub:
        tok_s = jax.ShapeDtypeStruct(
            (B, 1, cfg.d_model), cfg.dtype,
            sharding=NamedSharding(mesh, P(bspec, None, None)))
    else:
        tok_s = jax.ShapeDtypeStruct(
            (B, 1), jnp.int32, sharding=NamedSharding(mesh, P(bspec, None)))
    return serve_step, (params_s, tok_s, cache_s)


def build(cfg: ModelConfig, shape: InputShape, mesh, *,
          variant: str = "exact"):
    # Megatron-style activation constraints for the at-scale build.  The
    # residual anchor (model-axis replication of the stream) is required
    # for the vmapped per-worker TRAIN path; serving paths run leaner
    # without it — XLA keeps per-token ops sequence-sharded and gathers
    # only K/V (EXPERIMENTS.md §Perf).
    layers_lib.enable_activation_sharding(
        True, model_n=mesh_lib.model_size(mesh),
        anchor_residual=(shape.kind == "train"))
    if shape.kind == "train":
        return build_train(cfg, shape, mesh, variant=variant)
    if shape.kind == "prefill":
        return build_prefill(cfg, shape, mesh)
    return build_decode(cfg, shape, mesh)
