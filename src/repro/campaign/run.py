"""Campaign CLI: declarative grids -> batched engine -> resumable store.

    PYTHONPATH=src python -m repro.campaign.run --campaign table1 --seeds 5

Built-in campaigns (all multi-seed; the engine turns seeds and compatible
knob axes into vmap lanes, see ``engine.batch_key``):

  table1           paper Table 1: attack x defense grid
  fig2             paper Fig 2(b): variance attack x periodic reset
  alpha_sweep      n_byz 0..4 (alpha 0..0.4) x {variance, sign_flip}
                   x {safeguard_double, coord_median}
  threshold_sweep  safeguard threshold_floor sweep under the variance
                   attack (single + double guard) — one program per
                   defense, every floor a vmap lane
  adaptive         feedback-coupled adversaries (DESIGN.md §11) x
                   {safeguard_double, mean} — the adapt_* controller
                   knobs are vmap lanes like seeds
  defense          the history-aware defense zoo (DESIGN.md §12) x
                   {variance, adaptive_flip} — clip/spectral knobs are
                   vmap lanes like seeds
  hetero           worker-heterogeneity subsystem (DESIGN.md §13):
                   Dirichlet label-skew alpha sweep x defense x attack
                   plus a teacher-rotation concept-shift block — the
                   hetero_alpha/hetero_shift knobs are vmap lanes
  saddle           saddle-escape verification testbed (DESIGN.md §14):
                   planted-saddle task x defense x attack, reporting
                   escape-step distributions — safeguard + sgd_escape
                   noise escapes within the theorem's budget while the
                   undefended mean under saddle_push provably stalls
                   (use --steps 400 for the separation; the
                   saddle_gap / noise_r / vr_period knobs are vmap lanes)
  live             live-monitoring demo grid (DESIGN.md §17): one clean
                   lane that must stay alert-free, the variance attack
                   vs the safeguard (eviction storm fires as the
                   colluders are caught) and vs the undefended mean
                   (no evictions — only the loss stream tells the story)
  smoke            2x2 mini-grid for CI / tests

A second invocation with the same arguments runs 0 new cells (the store
is keyed by scenario content hash); extending ``--seeds`` or a campaign's
axis lists only runs the delta.

``--tap-every K`` streams a typed heartbeat (``repro.obs.schema.TAP``)
every K steps from each running lane into ``<store>/live/<cell>.jsonl``
(``repro.obs.live``); ``--watch`` echoes each beat as a progress line as
it arrives.  Tail a running campaign from another terminal with

    PYTHONPATH=src python -m repro.obs.live tail --campaign live
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Callable, Dict, List

from repro.campaign import engine
from repro.campaign.scenario import (ADAPTIVE_ATTACKS, HETERO_DEFENSES,
                                     Scenario, TABLE1_ATTACKS,
                                     TABLE1_DEFENSES, ZOO_DEFENSES,
                                     expand_grid, scenario_id, with_seeds)
from repro.campaign.store import DEFAULT_ROOT, CampaignStore
from repro.launch.compile_cache import enable_compile_cache


def _table1(seeds: int, steps: int) -> List[Scenario]:
    grid = expand_grid(attack=list(TABLE1_ATTACKS),
                       defense=list(TABLE1_DEFENSES), steps=[steps])
    return with_seeds(grid, seeds)


def _fig2(seeds: int, steps: int) -> List[Scenario]:
    grid = expand_grid(attack=["variance"], defense=["safeguard_double"],
                       reset_period=[0, 40, 80], steps=[steps])
    return with_seeds(grid, seeds)


def _alpha_sweep(seeds: int, steps: int) -> List[Scenario]:
    grid = expand_grid(attack=["variance", "sign_flip"],
                       defense=["safeguard_double", "coord_median"],
                       n_byz=[0, 1, 2, 3, 4], steps=[steps])
    return with_seeds(grid, seeds)


def _threshold_sweep(seeds: int, steps: int) -> List[Scenario]:
    grid = expand_grid(attack=["variance"],
                       defense=["safeguard_single", "safeguard_double"],
                       threshold_floor=[0.05, 0.1, 0.3, 1.0, 3.0],
                       steps=[steps])
    return with_seeds(grid, seeds)


def _defense(seeds: int, steps: int) -> List[Scenario]:
    """The history-aware defense zoo (DESIGN.md §12) under the attack the
    paper says historyless defenses cannot survive (variance) and the
    strongest feedback-coupled adversary (adaptive_flip)."""
    grid = expand_grid(attack=["variance", "adaptive_flip"],
                       defense=list(ZOO_DEFENSES), steps=[steps])
    return with_seeds(grid, seeds)


def _adaptive(seeds: int, steps: int) -> List[Scenario]:
    """Feedback-coupled adversaries (DESIGN.md §11) against the safeguard
    and the no-defense baseline: the threshold tracker must degrade
    ``mean`` while SafeguardSGD stays within noise of its static rows."""
    grid = expand_grid(attack=list(ADAPTIVE_ATTACKS),
                       defense=["safeguard_double", "mean"], steps=[steps])
    return with_seeds(grid, seeds)


def _hetero(seeds: int, steps: int) -> List[Scenario]:
    """Worker-heterogeneity campaign (DESIGN.md §13): non-IID honest
    workers are where selection-style defenses (krum, trimmed_mean)
    falsely evict honest outliers and where bucketing repairs them.
    Dirichlet label-skew alpha sweep (every alpha a vmap lane) across
    the hetero defense suite under {no attack, variance, adaptive_flip},
    plus a teacher-rotation concept-shift block."""
    alphas = [0.05, 1.0, 10.0]
    attacks = ["none", "variance", "adaptive_flip"]
    no_sg = [d for d in HETERO_DEFENSES if d != "safeguard_double"]
    grid = expand_grid(hetero=["dirichlet"], hetero_alpha=alphas,
                       attack=attacks, defense=no_sg, steps=[steps])
    # the safeguard runs both its IID calibration (eviction multiplier
    # 1.5 — shows the concentration filter stressed by honest skew) and
    # the zeta-relaxed lane (2.0 — evicts nobody, still catches the
    # variance colluders); both scales are lanes of one program
    grid += expand_grid(hetero=["dirichlet"], hetero_alpha=alphas,
                        attack=attacks, defense=["safeguard_double"],
                        threshold_scale=[1.5, 2.0], steps=[steps])
    grid += expand_grid(hetero=["shift"], hetero_shift=[0.5, 1.5],
                        attack=["none", "variance"],
                        defense=["mean", "safeguard_double",
                                 "centered_clip"], steps=[steps])
    return with_seeds(grid, seeds)


def _saddle(seeds: int, steps: int) -> List[Scenario]:
    """Saddle-escape verification campaign (DESIGN.md §14): both planted
    task kinds x {theorem row, stall row}.  The theorem row is
    safeguard_double + sgd_escape perturbation (clean, attacked, and an
    SVRG-anchored lane); the stall row is the undefended mean under the
    curvature-aware saddle_push colluders (boost ramps against null
    feedback, so the iterate stays pinned at the saddle: escape_step
    stays -1).  Run with --steps 400 to see the separation; see
    ``escape_budget`` for the predicted bound."""
    base = dict(d_in=[16], lr=[0.1], batch=[40], noise_r=[0.05],
                saddle_gap=[0.5, 1.0], steps=[steps])
    grid: List[Scenario] = []
    for task in ("saddle_quad", "saddle_chain"):
        # theorem row: clean, SVRG-anchored, and attacked lanes (the
        # clean vr_period 0/8 cells are lanes of one program)
        grid += expand_grid(task=[task], defense=["safeguard_double"],
                            perturb=["sgd_escape"], escape_nu=[0.1],
                            attack=["none"], vr_period=[0, 8], **base)
        grid += expand_grid(task=[task], defense=["safeguard_double"],
                            perturb=["sgd_escape"], escape_nu=[0.1],
                            attack=["saddle_push"], adapt_init=[1.0],
                            **base)
        # stall row: undefended mean under the saddle-point attack
        grid += expand_grid(task=[task], defense=["mean"],
                            attack=["saddle_push"], adapt_init=[1.0],
                            **base)
    return with_seeds(grid, seeds)


def _live(seeds: int, steps: int) -> List[Scenario]:
    """Live-monitoring demo grid (DESIGN.md §17).  Three lanes: a clean
    safeguard run (the alert catalog must stay silent on it — the
    ``live-smoke`` CI gate asserts exactly that), the variance attack
    against the safeguard (the eviction-storm rule fires as the
    colluders are caught), and the same attack against the undefended
    mean (nothing is ever evicted; only the loss stream shows the
    damage)."""
    grid = expand_grid(attack=["none"], defense=["safeguard_double"],
                       steps=[steps])
    grid += expand_grid(attack=["variance"],
                        defense=["safeguard_double", "mean"],
                        steps=[steps])
    return with_seeds(grid, seeds)


def _smoke(seeds: int, steps: int) -> List[Scenario]:
    grid = expand_grid(attack=["sign_flip", "variance"],
                       defense=["safeguard_double", "coord_median"],
                       steps=[steps])
    return with_seeds(grid, seeds)


CAMPAIGNS: Dict[str, Callable[[int, int], List[Scenario]]] = {
    "table1": _table1,
    "fig2": _fig2,
    "alpha_sweep": _alpha_sweep,
    "threshold_sweep": _threshold_sweep,
    "adaptive": _adaptive,
    "defense": _defense,
    "hetero": _hetero,
    "saddle": _saddle,
    "live": _live,
    "smoke": _smoke,
}


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser(
        description="run a scenario campaign through the batched engine")
    ap.add_argument("--campaign", required=True, choices=sorted(CAMPAIGNS))
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--steps", type=int, default=None,
                    help="steps per trial (default 150; --quick default 40)")
    ap.add_argument("--quick", action="store_true",
                    help="short trials (40 steps unless --steps is given)")
    ap.add_argument("--root", default=DEFAULT_ROOT,
                    help="store root (experiments/campaigns)")
    ap.add_argument("--store-traces", action="store_true",
                    help="persist per-step metric traces as compressed "
                         ".npz sidecars under <store>/traces/ "
                         "(repro.obs.trace; event logs are always stored)")
    ap.add_argument("--loop", action="store_true",
                    help="run lanes unbatched (debugging / A-B timing)")
    ap.add_argument("--tap-every", type=int, default=0, metavar="K",
                    help="stream a live heartbeat every K steps per lane "
                         "into <store>/live/ (repro.obs.live; 0 = off)")
    ap.add_argument("--watch", action="store_true",
                    help="echo each heartbeat as a per-cell progress "
                         "line (implies --tap-every 50 if unset)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    steps = args.steps if args.steps is not None else (40 if args.quick
                                                       else 150)
    scenarios = CAMPAIGNS[args.campaign](args.seeds, steps)
    store = CampaignStore(args.campaign, root=args.root)
    pending = store.pending(scenarios)
    done = len(scenarios) - len(pending)
    print(f"campaign,{args.campaign},cells={len(scenarios)},done={done},"
          f"new_cells={len(pending)}")

    tap_every = args.tap_every or (50 if args.watch else 0)
    collector = None
    if tap_every:
        from repro.obs import live as live_lib

        # lazy file creation inside the collector keeps a resume run
        # (0 pending cells -> 0 heartbeats) byte-identical on disk
        collector = live_lib.LiveCollector(
            name=args.campaign,
            heartbeat_dir=os.path.join(store.dir, live_lib.LIVE_DIR),
            echo=((lambda line: print(f"live,{line}", flush=True))
                  if args.watch else None))

    t0 = time.time()
    if pending:
        n_groups = len(engine.group_scenarios(pending))
        print(f"campaign,{args.campaign},groups={n_groups}")
        results = engine.run_scenarios(pending, batched=not args.loop,
                                       verbose=True, collector=collector,
                                       tap_every=tap_every)
        for s in pending:
            rec = results[scenario_id(s)]
            store.append(s, rec, store_traces=args.store_traces)
            caught = rec.get("caught_byz", "-")
            zeta = rec.get("zeta_sq_mean")
            zeta = f",zeta_sq={zeta:.4g}" if zeta is not None else ""
            esc = rec.get("escape_step")
            esc = f",escape_step={esc}" if esc is not None else ""
            print(f"campaign,{args.campaign},{s.attack},{s.defense},"
                  f"seed={s.seed},acc={rec['acc']:.4f},caught={caught}"
                  f"{zeta}{esc}")
    wall = time.time() - t0
    if collector is not None:
        collector.close()
        print(f"campaign,{args.campaign},heartbeats={len(collector.ring)},"
              f"dropped={collector.dropped}")
    store.write_meta({"campaign": args.campaign, "seeds": args.seeds,
                      "steps": steps, "cells": len(scenarios),
                      "last_new_cells": len(pending),
                      "last_wall_s": round(wall, 2)})
    print(f"campaign,{args.campaign},ran={len(pending)},"
          f"wall_s={wall:.1f},store={store.path}")
    return {"cells": len(scenarios), "ran": len(pending), "wall_s": wall,
            "store": store.path}


if __name__ == "__main__":
    main()
