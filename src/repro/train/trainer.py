"""Byzantine-resilient training loop.

``make_train_step`` builds one jitted step implementing the paper's
master/worker protocol in SPMD form:

  1. per-worker gradients — ``vmap`` of ``value_and_grad`` over the worker
     axis of the batch (leaves (m, B/m, ...)); under the production mesh
     the worker axis is sharded over ``data`` so each data shard computes
     exactly one worker's gradient (DESIGN.md §3);
  2. the Byzantine simulation — an attack from ``core.attacks`` rewrites
     the rows of the stacked gradient marked by ``byz_mask``; adaptive
     attacks additionally ``observe`` the defense's public outputs of the
     previous step (good mask, thresholds, median distances — DESIGN.md
     §11), threaded through ``TrainState.attack_state`` so the feedback
     loop survives ``scan_trial``/vmap;
  3. aggregation — ONE ``core.defenses.Defense`` object (DESIGN.md §12):
     SafeguardSGD, a historyless baseline, or a history-aware zoo
     defense (centered clipping, norm filter, DnC, compositions).  Its
     state — the safeguard's flat ``(m, d_pad)`` accumulators, momentum
     buffers, EMA scalars — is threaded through
     ``TrainState.defense_state``; flat buffers keep their worker rows
     pinned to the ``data`` mesh axes via ``acc_sharding``, so windowed
     accumulates stay shard-local and only the ``(m, m)`` distance
     matrix crosses shards;
  4. the optimizer update.

``Trainer`` wraps the step with a plain python loop, metric collection and
checkpointing for the benchmarks/examples.  ``scan_trial`` rolls an entire
trial (data generation + step) into one ``lax.scan`` so a full training
run is a single device program — the campaign engine
(``repro.campaign.engine``) builds on it to ``vmap`` whole trials over
seeds and scenario knobs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregators as agg_lib
from repro.core import attacks as atk_lib
from repro.core import defenses as dfn_lib
from repro.core import safeguard as sg
from repro.core import tree_utils as tu
from repro.data import hetero as het_lib
from repro.obs import schema as obs_schema
from repro.optim import OptimizerBundle

f32 = jnp.float32


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    defense_state: Any
    attack_state: Any
    step: jax.Array
    rng: jax.Array

    @property
    def sg_state(self):
        """Back-compat alias from the pre-protocol era, when the
        safeguard was the only stateful defense."""
        return self.defense_state


def resolve_defense(defense: Optional[dfn_lib.Defense] = None,
                    sg_cfg: Optional[sg.SafeguardConfig] = None,
                    aggregator: Optional[agg_lib.Aggregator] = None
                    ) -> dfn_lib.Defense:
    """One :class:`core.defenses.Defense` from the new (``defense=``) or
    legacy (``sg_cfg=`` / ``aggregator=``) spellings."""
    if defense is not None:
        if sg_cfg is not None or aggregator is not None:
            raise ValueError("pass either defense or sg_cfg/aggregator, "
                             "not both")
        return defense
    if (sg_cfg is None) == (aggregator is None):
        raise ValueError("pass exactly one of sg_cfg / aggregator")
    if sg_cfg is not None:
        return dfn_lib.make_safeguard_defense(sg_cfg)
    return dfn_lib.from_aggregator(aggregator)


def init_train_state(params, opt: OptimizerBundle, *,
                     defense: Optional[dfn_lib.Defense] = None,
                     sg_cfg: Optional[sg.SafeguardConfig] = None,
                     aggregator: Optional[agg_lib.Aggregator] = None,
                     attack: Optional[atk_lib.Attack] = None,
                     seed: int = 0) -> TrainState:
    defense_state = None
    if defense is not None or sg_cfg is not None:
        d = resolve_defense(defense, sg_cfg, aggregator)
        if d.init_state is not None:
            defense_state = d.init_state(params)
    attack_state = (attack.init(params)
                    if attack is not None and attack.init is not None
                    else None)
    return TrainState(params=params, opt_state=opt.init(params),
                      defense_state=defense_state,
                      attack_state=attack_state,
                      step=jnp.zeros((), jnp.int32),
                      rng=jax.random.PRNGKey(seed))


def zeno_scores(loss_fn, params, grads, held_batch, *, eta: float,
                rho: float):
    """Zeno's stochastic descendant score per worker (Definition C.4):
    Score(g_i) = f_r(x) - f_r(x - eta g_i) - rho ||g_i||^2 evaluated on a
    held-out minibatch (the master-side oracle)."""
    loss_before = loss_fn(params, held_batch)

    def one(g_row):
        stepped = jax.tree.map(
            lambda p, g: (p.astype(f32) - eta * g.astype(f32)
                          ).astype(p.dtype), params, g_row)
        return loss_fn(stepped, held_batch)

    loss_after = jax.vmap(one)(grads)
    # per-row squared norms (O(m d)) — NOT the full (m, m) Gram, whose
    # only consumed entries would be its diagonal
    sq = tu.tree_row_sq_norms(grads)
    return loss_before - loss_after - rho * sq


def make_train_step(loss_fn: Callable, opt: OptimizerBundle, *,
                    byz_mask: jax.Array,
                    defense: Optional[dfn_lib.Defense] = None,
                    sg_cfg: Optional[sg.SafeguardConfig] = None,
                    aggregator: Optional[agg_lib.Aggregator] = None,
                    attack: Optional[atk_lib.Attack] = None,
                    zeno_eta: float = 0.1, zeno_rho: float = 5e-4,
                    spmd_axis_name=None, acc_sharding=None,
                    sg_acc_sharding=None, trace_zeta: bool = True,
                    perturb: str = "none", escape_nu=0.0,
                    escape_thresh=0.1,
                    so_probe: Optional[Callable] = None,
                    has_aux: bool = False,
                    jit: bool = True):
    """Build the jitted training step.

    The defense is one :class:`core.defenses.Defense` (``defense=``);
    the legacy spellings ``sg_cfg=`` (the paper's safeguard) and
    ``aggregator=`` (a historyless baseline) are resolved through the
    same protocol.  ``loss_fn(params, worker_batch) -> scalar``.

    ``spmd_axis_name``: mesh axis (or tuple) carrying the worker dimension
    at scale — passed to ``vmap`` so every per-worker intermediate keeps
    its data-axis sharding through the backward pass (without it XLA's
    propagation drops the worker sharding inside the layer scan and
    replicates multi-GiB attention buffers).

    ``acc_sharding``: optional ``NamedSharding`` for the defense's flat
    ``(m, d_pad)`` state buffers (see ``launch.sharding.flat_acc_pspec``);
    ``None`` on a single device.  ``sg_acc_sharding`` is the deprecated
    alias.

    ``perturb="sgd_escape"`` enables the paper's saddle-escape
    perturbation (DESIGN.md §14): when the aggregated direction's norm
    falls to ``escape_thresh`` or below — the master's observable proxy
    for "near a stationary point" — isotropic ``N(0, escape_nu^2 I)``
    noise is added to it.  Injected *after* aggregation, so Byzantine
    workers can only react to the draw one step late.  ``escape_nu`` /
    ``escape_thresh`` may be traced scalars (campaign vmap knobs); the
    mode itself is program structure (it consumes an extra rng split).

    ``so_probe``: optional pure function ``params -> {name: scalar}``
    traced into the metrics every step — the second-order trace lane of
    the planted-saddle testbed (``data.saddle.make_probe``: the analytic
    ``true_grad_norm`` / ``min_eig_proxy`` / ``escaped``).

    ``has_aux``: ``loss_fn`` returns ``(loss, {name: scalar})``; each
    name becomes a per-worker ``(m,)`` metric (the held experts' row
    counts of ``models.transformer.loss_and_load``).
    """
    defense = resolve_defense(defense, sg_cfg, aggregator)
    if acc_sharding is None:
        acc_sharding = sg_acc_sharding
    attack = attack or atk_lib.Attack("none", atk_lib.attack_none)
    if perturb not in ("none", "sgd_escape"):
        raise ValueError(f"unknown perturbation mode {perturb!r} "
                         "(one of 'none', 'sgd_escape')")
    m = int(byz_mask.shape[0])
    scalar_loss = (lambda p, b: loss_fn(p, b)[0]) if has_aux else loss_fn

    def step_fn(state: TrainState, batch, held_batch=None):
        if perturb == "sgd_escape":
            rng, k_attack, k_noise, k_escape = jax.random.split(state.rng, 4)
        else:
            rng, k_attack, k_noise = jax.random.split(state.rng, 3)

        # (1) per-worker gradients.  Each phase runs under a named scope
        # (fwd_bwd, attack, defense, optimizer, telemetry) that reaches the
        # compiled step's op_name metadata: the device trace's per-phase
        # times are read by these names
        with jax.named_scope("fwd_bwd"):
            vg = jax.value_and_grad(loss_fn, has_aux=has_aux)
            out, grads = jax.vmap(lambda wb: vg(state.params, wb),
                                  spmd_axis_name=spmd_axis_name)(batch)
            losses, loss_aux = out if has_aux else (out, {})

        # (2) Byzantine simulation — the attack state already absorbed the
        # previous step's public defense feedback (observe, below)
        with jax.named_scope("attack"):
            grads, attack_state = attack.act(grads, byz_mask,
                                             state.attack_state, state.step,
                                             k_attack)

        # (3) aggregation through the Defense protocol (DESIGN.md §12)
        with jax.named_scope("telemetry"):
            metrics: Dict[str, jax.Array] = {
                "loss": losses.mean(),
                "honest_loss": (losses * (~byz_mask)).sum()
                / jnp.maximum((~byz_mask).sum(), 1),
                **loss_aux,
            }
        ctx = {"rng": k_noise, "acc_sharding": acc_sharding}
        with jax.named_scope("defense"):
            if defense.needs_held_batch:
                if held_batch is None:
                    raise ValueError(f"{defense.name} needs a held-out batch")
                ctx["scores"] = zeno_scores(scalar_loss, state.params, grads,
                                            held_batch, eta=zeno_eta,
                                            rho=zeno_rho)
            agg, defense_state, info = defense.aggregate(state.defense_state,
                                                         grads, ctx)
        # flight-recorder schema check (DESIGN.md §15): tracer shapes and
        # dtypes are static, so this runs once per program trace and is
        # free per step — a defense renaming a key or changing a shape
        # class fails loudly here instead of corrupting campaign traces
        obs_schema.validate_info(info, m, where=f"defense:{defense.name}")
        with jax.named_scope("telemetry"):
            # dissimilarity-aware trace layer (DESIGN.md §13): the measured
            # zeta^2 heterogeneity of the reported gradients — over the
            # simulation's ground-truth honest set and over the defense's
            # live good set (what a real master could compute).  Two O(m d)
            # passes; ``trace_zeta=False`` drops them from the hot path
            # (the at-scale lowering of launch/specs does)
            if trace_zeta:
                metrics["zeta_sq"] = het_lib.zeta_sq(grads, ~byz_mask)
                metrics["zeta_good_sq"] = het_lib.zeta_sq(grads,
                                                          info["good"])
            if defense.stateful:
                metrics["n_good"] = info["n_good"]
                metrics["caught_byz"] = (byz_mask & ~info["good"]).sum()
                metrics["evicted_honest"] = (~byz_mask & ~info["good"]).sum()
                metrics["good"] = info["good"]
                if "restored" in info:
                    metrics["restored"] = info["restored"].sum()
            # per-worker detection statistics + live thresholds, traced
            # when the defense publishes them — the obs event layer
            # reconstructs evictions/threshold-crossings from exactly these
            # surfaces (Fig-2a reads them from the engine's traces instead
            # of re-implementing the training loop)
            for k in ("dist_to_med_B", "dist_to_med_A",
                      "threshold_B", "threshold_A"):
                if k in info:
                    metrics[k] = jnp.asarray(info[k], jnp.float32)
            # adaptive-attack controller level consumed by this step's
            # act() (observe has not folded this step's feedback yet) — its
            # reversals are the attack's phase boundaries
            if attack.observe is not None:
                lvl = atk_lib.controller_level(state.attack_state)
                if lvl is not None:
                    metrics["attack_level"] = lvl
            # second-order trace lane (DESIGN.md §14): analytic saddle
            # diagnostics of the current iterate, traced like zeta_sq
            if so_probe is not None:
                metrics.update(so_probe(state.params))
        # the paper's saddle-escape perturbation: isotropic noise on the
        # aggregated direction when its norm says "near-stationary"
        if perturb == "sgd_escape":
            with jax.named_scope("optimizer"):
                agg_norm = jnp.sqrt(tu.tree_sq_norm(agg))
                on = (agg_norm <= jnp.asarray(escape_thresh, f32)
                      ).astype(f32)
                leaves = jax.tree_util.tree_leaves(agg)
                keys = iter(list(jax.random.split(k_escape, len(leaves))))

                def _noise(leaf):
                    k = next(keys)
                    xi = jax.random.normal(k, leaf.shape, f32)
                    return (leaf.astype(f32)
                            + on * jnp.asarray(escape_nu, f32) * xi
                            ).astype(leaf.dtype)
                agg = jax.tree.map(_noise, agg)
            metrics["escape_on"] = on

        # feedback coupling (DESIGN.md §11): adaptive attacks fold this
        # step's public defense outputs into the state the next step's
        # act() will read — the carry keeps the loop scan/vmap-able
        with jax.named_scope("attack"):
            feedback = atk_lib.defense_feedback(info, m)
            if attack.observe is not None:
                attack_state = attack.observe(attack_state, feedback,
                                              byz_mask)

        # (4) optimizer
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update(agg, state.opt_state,
                                           state.params, state.step)
        with jax.named_scope("telemetry"):
            metrics["grad_norm"] = jnp.sqrt(tu.tree_sq_norm(agg))
        obs_schema.validate_metrics(metrics, m,
                                    where=f"train_step:{defense.name}")
        new_state = TrainState(params=params, opt_state=opt_state,
                               defense_state=defense_state,
                               attack_state=attack_state,
                               step=state.step + 1, rng=rng)
        return new_state, metrics

    return jax.jit(step_fn) if jit else step_fn


def _select_traces(metrics: Dict, trace_fields) -> Dict:
    if trace_fields is None:
        return metrics
    unknown = [k for k in trace_fields if k not in metrics]
    if unknown:
        raise ValueError(
            f"scan_trial: unknown trace field(s) {unknown}; this "
            f"step emits {sorted(metrics)}")
    return {k: metrics[k] for k in trace_fields}


def tap_payload(metrics: Dict, state: TrainState,
                tap_meta: Optional[Dict] = None) -> Dict:
    """Reduce a ``(K, ...)``-stacked window of step metrics to the
    bounded scalar payload of one heartbeat (the tap surface of
    ``repro.obs.schema``): window ``mean`` for loss-like keys, window
    ``last`` for live state, ``tap_meta`` scalars (lane identity)
    merged in verbatim.  Pure; runs inside the outer scan body."""
    payload: Dict[str, jax.Array] = {
        "step": jnp.asarray(state.step, jnp.int32)}
    for name in obs_schema.DEVICE_TAP_KEYS:
        spec = obs_schema.TAP[name]
        if name == "step" or name not in metrics:
            continue
        col = metrics[name]
        val = col.mean() if spec.agg == "mean" else col[-1]
        payload[name] = jnp.asarray(val, spec.dtype)
    if tap_meta:
        for name, val in tap_meta.items():
            payload[name] = jnp.asarray(val)
    return obs_schema.validate_tap(payload, where="scan_trial.tap")


def scan_trial(step_fn, state: TrainState, *, batch_fn, steps: int,
               held_fn=None, trace_fields=None, tap_every: int = 0,
               tap: Optional[Callable] = None, tap_meta=None):
    """Roll a whole training trial into one ``lax.scan``.

    ``step_fn`` must be the *unjitted* step (``make_train_step(...,
    jit=False)``) — its carry (:class:`TrainState`) already threads the
    optimizer, defense and attack state pytrees, which is exactly what
    makes the loop body scan-able (and, one level up, vmap-able over
    seeds/scenario knobs).

    ``batch_fn(t) -> worker batch`` and ``held_fn(t) -> held-out batch``
    regenerate the data *inside* the scan body from the step index — they
    must be pure jax functions (the seeded synthetic pipelines in
    ``repro.data`` are; see ``teacher_batches``'s fold_in scheme).

    ``trace_fields``: optional subset of metric names to stack over the
    step axis (default: all metrics the step emits).  ``()`` traces
    nothing (the scan carries no ys, so trace memory is zero); a name the
    step does not emit raises :class:`ValueError` at trace time, naming
    both the offender and the available fields.

    ``tap_every=K`` with a host callable ``tap`` streams a bounded
    scalar summary of every K-step window (:func:`tap_payload`, typed by
    ``repro.obs.schema.TAP``) through ``jax.experimental.io_callback``
    — the live-telemetry layer (DESIGN.md §17).  The scan is then
    nested: an outer scan over ``steps // K`` windows whose body is an
    inner scan over K steps plus one unconditional callback.  The
    nesting is what keeps the callback legal under the campaign
    engine's vmap (``io_callback`` under ``vmap``-of-``cond`` is
    unsupported) and changes **nothing** about the computation: the
    step sequence, rng stream and stacked traces are bit-identical to
    the flat scan (locked by tests/test_live.py).  ``steps`` must be a
    multiple of K.  Under vmap the callback fires once per lane per
    window with unbatched scalars and no lane identity — thread one
    through ``tap_meta`` (a dict of traced scalars merged into every
    payload, e.g. ``{"lane": knobs["lane"]}``).  ``tap_every=0``
    (default) is byte-for-byte the untapped program.

    Returns ``(final_state, traces)`` with each trace leaf shaped
    ``(steps, ...)``.
    """
    def body(st, t, _keep=trace_fields):
        batch = batch_fn(t)
        if held_fn is not None:
            st, metrics = step_fn(st, batch, held_fn(t))
        else:
            st, metrics = step_fn(st, batch)
        return st, _select_traces(metrics, _keep)

    if not tap_every:
        return jax.lax.scan(body, state, jnp.arange(steps))

    if tap is None:
        raise ValueError("scan_trial: tap_every > 0 needs a host `tap` "
                         "callable (see repro.obs.live.LiveCollector)")
    K = int(tap_every)
    if K < 0 or steps % K != 0:
        raise ValueError(
            f"scan_trial: steps ({steps}) must be a positive multiple of "
            f"tap_every ({K}) — windows must tile the trial exactly so "
            "the tapped step sequence is the untapped one")
    from jax.experimental import io_callback

    def window(st, ts):
        # full metrics as inner ys (the payload may need keys outside
        # trace_fields); filtered down before they reach the outer ys
        st, mets = jax.lax.scan(lambda s, t: body(s, t, _keep=None),
                                st, ts)
        payload = tap_payload(mets, st, tap_meta)
        io_callback(tap, None, payload)
        return st, _select_traces(mets, trace_fields)

    final, traces = jax.lax.scan(window, state,
                                 jnp.arange(steps).reshape(steps // K, K))
    traces = jax.tree.map(
        lambda a: a.reshape((steps,) + tuple(a.shape[2:])), traces)
    return final, traces


class Trainer:
    """Python-loop wrapper: data iterators, metrics history, eval hooks.

    Interactive logging goes through the same live-telemetry path as
    campaign cells (``repro.obs.live.LiveCollector``, DESIGN.md §17):
    at every ``log_every`` boundary the scalar record's tap-surface
    subset becomes one heartbeat — ring-buffered, optionally persisted
    (pass a ``collector`` with a ``heartbeat_dir``), and echoed to the
    terminal when ``verbose``.  Scalar ``history`` is unchanged by any
    of this."""

    def __init__(self, state: TrainState, step_fn, data_iter, *,
                 held_iter=None, eval_fn: Optional[Callable] = None,
                 log_every: int = 50, name: str = "run", collector=None):
        self.state = state
        self.step_fn = step_fn
        self.data_iter = data_iter
        self.held_iter = held_iter
        self.eval_fn = eval_fn
        self.log_every = log_every
        self.name = name
        self.collector = collector
        self.history: list = []
        # non-scalar metrics are trace material, not history lines: they
        # accumulate here every step (as device arrays — no host sync)
        # and trace_arrays() stacks them, matching scan_trial's layout
        self.traces: Dict[str, list] = {}
        self._routed_keys: set = set()
        # steps dispatched by run(): names each step's profiler span
        # without reading ``state.step`` back from the device
        self.dispatched = 0

    def trace_arrays(self) -> Dict[str, "np.ndarray"]:
        """Stack the accumulated per-step vector metrics into
        ``(steps, ...)`` numpy arrays — the same dense-trace layout
        ``scan_trial`` returns, consumable by ``repro.obs.events``."""
        return {k: np.stack([np.asarray(v) for v in vs])
                for k, vs in self.traces.items()}

    def run(self, steps: int, verbose: bool = True):
        """Dispatch ``steps`` steps.  Each runs inside the profiler span
        ``repro.step`` (``step_num`` = :attr:`dispatched`, a host counter,
        so no step waits on the device to be named), with the spans
        ``repro.batch`` (the iterators), ``repro.dispatch`` (the step
        call) and ``repro.log`` (the log-boundary record, which reads the
        device) inside it.  Off the profiler each span costs about a
        microsecond of host time."""
        collector = self.collector
        if collector is None and verbose:
            from repro.obs import live as live_lib
            collector = self.collector = live_lib.LiveCollector(
                name=self.name, echo=print)
        t0 = time.time()
        for i in range(steps):
            with jax.profiler.StepTraceAnnotation("repro.step",
                                                  step_num=self.dispatched):
                with jax.profiler.TraceAnnotation("repro.batch"):
                    batch = next(self.data_iter)
                    held = (next(self.held_iter)
                            if self.held_iter is not None else None)
                with jax.profiler.TraceAnnotation("repro.dispatch"):
                    if held is not None:
                        self.state, metrics = self.step_fn(self.state, batch,
                                                           held)
                    else:
                        self.state, metrics = self.step_fn(self.state, batch)
                self.dispatched += 1
                # route non-scalar metrics to the trace path (history holds
                # scalars only); surface what was routed once per run so
                # the keys are not silently invisible
                vec = {k: v for k, v in metrics.items()
                       if getattr(v, "ndim", 0) != 0}
                for k, v in vec.items():
                    self.traces.setdefault(k, []).append(v)
                new_keys = set(vec) - self._routed_keys
                if new_keys:
                    self._routed_keys |= new_keys
                    if verbose:
                        print(f"[{self.name}] non-scalar metrics routed to "
                              f".traces (not history): {sorted(new_keys)}")
                if (i + 1) % self.log_every == 0 or i == steps - 1:
                    with jax.profiler.TraceAnnotation("repro.log"):
                        rec = {k: float(v) for k, v in metrics.items()
                               if getattr(v, "ndim", 0) == 0}
                        rec["step"] = int(self.state.step)
                        if self.eval_fn is not None:
                            rec.update(self.eval_fn(self.state.params))
                        rec["wall_s"] = time.time() - t0
                        self.history.append(rec)
                        # one telemetry path for interactive runs and
                        # campaign cells: the record's tap-surface subset is
                        # a heartbeat (the collector stamps step_rate/t_wall
                        # and echoes it)
                        if collector is not None:
                            collector.tap({k: v for k, v in rec.items()
                                           if k in obs_schema.TAP})
        return self.history
