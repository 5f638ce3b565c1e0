"""Training driver.

Runs a real (CPU-feasible) Byzantine training experiment on the reduced
configs: pick an architecture, an attack, a defense, and go.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 200 --attack sign_flip --defense safeguard \
        --workers 10 --byz 4

For the at-scale (256/512-chip) lowering of the same step, use
``repro.launch.dryrun`` — this driver is the runnable end-to-end path.
"""

from __future__ import annotations

import argparse
import functools
import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as C
from repro.configs.base import TrainConfig
from repro.core import attacks as atk_lib
from repro.core import defenses as dfn_lib
from repro.data import pipeline as data_lib
from repro.launch import sharding as sh
from repro.launch import specs as specs_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.train import Trainer, init_train_state, make_train_step
from repro import checkpoint as ckpt_lib


def build_defense(name: str, m: int, n_byz: int,
                  args) -> dfn_lib.Defense:
    """Any defense of the protocol registry (DESIGN.md §12);
    ``safeguard`` is an alias for ``safeguard_double``."""
    if name == "safeguard":
        name = "safeguard_double"
    reg = dfn_lib.make_registry(m, n_byz, T0=args.t0, T1=args.t1,
                                threshold_floor=args.floor,
                                reset_period=args.reset_period,
                                use_sketch=args.sketch)
    if name not in reg:
        raise SystemExit(f"unknown defense {name}; "
                         f"choose safeguard|{sorted(reg)}")
    return reg[name]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--full", action="store_true",
                    help="full config (default: reduced smoke config)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=80)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--workers", type=int, default=10)
    ap.add_argument("--byz", type=int, default=4)
    ap.add_argument("--attack", default="sign_flip",
                    choices=sorted(atk_lib.make_registry()))
    ap.add_argument("--defense", default="safeguard")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
    ap.add_argument("--t0", type=int, default=50)
    ap.add_argument("--t1", type=int, default=200)
    ap.add_argument("--floor", type=float, default=1.0)
    ap.add_argument("--reset-period", type=int, default=0)
    ap.add_argument("--hetero-alpha", type=float, default=0.0,
                    help="Dirichlet worker heterogeneity on the token "
                         "stream (0 = IID, DESIGN.md §13); LM archs only")
    ap.add_argument("--sketch", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default=None, help="write history JSON here")
    return ap.parse_args(argv)


def _placed(batches, mesh, m: int):
    """Put each worker-stacked batch on ``mesh``, worker axis on ``data``."""
    for b in batches:
        specs = sh.batch_pspecs(b, mesh, m)
        yield jax.device_put(b, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)))


def _under_mesh(fn, mesh):
    """``fn`` traced with ``mesh`` as the context mesh, where the model's
    per-worker loops (``models.layers._lanes_in_turn``) find its worker
    axes."""
    @functools.wraps(fn)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)
    return traced


def build_trainer(cfg, args, *, mesh=None) -> Trainer:
    """The run's wiring: config -> defense -> ``make_train_step`` ->
    :class:`Trainer`, shared by :func:`main` and ``chip_smoke.py``.

    The step is jitted here with the :class:`TrainState` donated
    (``Trainer.run`` rebinds its state every step), so the defense's flat
    ``(m, d_pad)`` accumulators are updated in place rather than held
    twice.  With a ``mesh`` (axes ``data``/``model``, one worker row per
    ``data`` shard) state and batches are placed by the rules of
    ``launch.specs`` and the safeguard's accumulators are pinned to it,
    so it takes the shardable XLA distance pass (DESIGN.md §3); without
    one everything lives on the default device, where on a TPU the
    safeguard updates its f32 accumulators and their Grams by one
    in-place Pallas pass per gradient leaf (DESIGN.md §6)."""
    m, n_byz = args.workers, args.byz
    if args.batch % m:
        raise SystemExit("--batch must be divisible by --workers")
    byz_mask = jnp.arange(m) < n_byz

    attack = atk_lib.make_registry()[args.attack]
    defense = build_defense(args.defense, m, n_byz, args)

    opt = make_optimizer(TrainConfig(lr=args.lr, momentum=args.momentum,
                                     optimizer=args.optimizer))
    # the step reports each worker's held routed-expert load, if any
    loss = lambda p, b: T.loss_and_load(p, cfg, b)

    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    state = init_train_state(params, opt, defense=defense, attack=attack,
                             seed=args.seed)
    spmd_axis_name = acc_sharding = shardings = None
    if mesh is not None:
        shardings = specs_lib.train_state_shardings(state, mesh)
        state = jax.device_put(state, shardings)
        spmd_axis_name = "data"
        if defense.flat_state:
            acc_sharding = NamedSharding(mesh, sh.flat_acc_pspec(
                mesh, state.defense_state.layout.d_padded))
    step = make_train_step(loss, opt, byz_mask=byz_mask, defense=defense,
                           attack=attack, spmd_axis_name=spmd_axis_name,
                           acc_sharding=acc_sharding, has_aux=True,
                           jit=False)
    if mesh is not None:
        step = _under_mesh(step, mesh)
    # the new state keeps the placement of the donated one, so its buffers
    # are reused and the next step takes it as it is
    step = jax.jit(step, donate_argnums=0, out_shardings=(shardings, None))

    flip = byz_mask if attack.data_attack else None
    if cfg.embed_stub:
        if args.hetero_alpha > 0:
            raise SystemExit("--hetero-alpha models token streams; "
                             "stub-frontend archs have no token unigram "
                             "to skew")
        it = data_lib.stub_batches(cfg.d_model, cfg.vocab_size, args.batch,
                                   args.seq, seed=args.seed, m=m,
                                   flip_mask=flip)
    else:
        it = data_lib.lm_batches(cfg.vocab_size, args.batch, args.seq,
                                 seed=args.seed, m=m, flip_mask=flip,
                                 hetero_alpha=args.hetero_alpha)
    if mesh is not None:
        it = _placed(it, mesh, m)
    held = None
    if defense.needs_held_batch:
        if cfg.embed_stub:
            held = data_lib.stub_batches(cfg.d_model, cfg.vocab_size,
                                         8, args.seq, seed=args.seed + 1)
        else:
            held = data_lib.lm_batches(cfg.vocab_size, 8, args.seq,
                                       seed=args.seed + 1)

    name = f"{cfg.name}/{args.attack}/{args.defense}"
    return Trainer(state, step, it, held_iter=held,
                   log_every=args.log_every, name=name)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = C.get(args.arch) if args.full else C.get_smoke(args.arch)
    trainer = build_trainer(cfg, args)
    hist = trainer.run(args.steps)

    if args.ckpt_dir:
        ckpt_lib.save(args.ckpt_dir, int(trainer.state.step),
                      {"params": trainer.state.params},
                      metadata={"arch": cfg.name, "attack": args.attack,
                                "defense": args.defense})
        print(f"checkpoint written to {args.ckpt_dir}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"config": vars(args), "history": hist}, f, indent=1)
        print(f"history written to {args.out}")


if __name__ == "__main__":
    main()
