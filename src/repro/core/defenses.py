"""The unified stateful Defense protocol and the defense zoo.

The paper's central distinction — *historyless* aggregators fall to the
variance attack, windowed *history* survives it — used to be an
architectural split in this repo: ``core/aggregators.py`` was a bag of
pure functions, SafeguardSGD a bespoke stateful path with its own
``TrainState`` buffers, and the trainer/campaign special-cased each
(``needs_scores``, the ``safeguard_*`` name family).  This module is the
defense-side twin of the attack protocol (DESIGN.md §11): every defense
— historyless or not — is one frozen :class:`Defense` object

    init_state(grads_like)            -> state        [None = stateless]
    aggregate(state, grads, ctx)      -> (agg, state', info)

``grads`` is the worker-stacked gradient pytree (leaves ``(m, ...)``),
``ctx`` a dict of step-scoped resources the trainer provides (``rng``,
``scores`` from Zeno's held-batch oracle, ``acc_sharding`` for the flat
buffers).  ``info`` always carries ``good`` (the ``(m,)`` bool
membership mask this step aggregated over — all-True for non-filtering
defenses) and ``n_good``; filtering defenses additionally publish the
safeguard feedback keys (thresholds, distances to the concentration
median) that adaptive attacks observe (``attacks.defense_feedback``).

State is an ordinary pytree threaded through ``TrainState.defense_state``
— fixed shapes, no python branches — so whole trials stay
``lax.scan``-able and the campaign engine vmaps defense knobs
(``clip_tau``/``clip_beta``/``spectral_iters``, :data:`DEFENSE_DEFAULTS`)
exactly like the attack's ``adapt_*`` axes.

The zoo (:func:`make_registry`):

  * the seven historyless baselines (mean, coordinate median, trimmed
    mean, geometric medoid, Weiszfeld, Krum, Zeno) as trivially-stateless
    instances of the pure functions in ``core.aggregators``;
  * SafeguardSGD (single/double) — state IS the flat ``(m, d_pad)``
    accumulators of ``core.safeguard``;
  * ``centered_clip`` — centered clipping with per-worker server-side
    momentum [Karimireddy, He, Jaggi 2021; simplified convergence theory
    of Roberts & Smyth 2022]: history-aware, survives the variance
    attack without evicting anyone;
  * ``norm_filter`` — norm-threshold filtering against an EMA of the
    median reported norm (norm-thresholding defenses à la Sun et al.
    2019; the escape-saddle ByzantinePGD line of Yin et al. 2019 uses
    the same reject-by-magnitude primitive);
  * ``dnc`` — Divide-and-Conquer spectral filtering [Shejwalkar &
    Houmansadr 2021]: remove the ``n_byz`` workers with the largest
    projection onto the top singular direction of the centered gradient
    matrix, power iteration warm-started across steps;
  * ``safeguard_cclip`` — composition: the safeguard's windowed filter
    picks the good set, centered clipping aggregates over it;
  * ``bucketing_*`` — :func:`make_bucketing` [Karimireddy, He & Jaggi
    2022] as a *meta*-defense: per-step random s-bucket averaging in
    front of any wrapped aggregator (``bucketing_krum``,
    ``bucketing_cclip``), shrinking inter-worker heterogeneity by ~1/s
    before selection-style rules see the rows (DESIGN.md §13).

All stateful defenses operate on the flat ``(m, d_pad)`` buffer layout
of ``core.safeguard`` (one ``flatten_stacked`` per step), so the
pairwise-distance ones reuse the Pallas Gram kernel and the
``launch.sharding.flat_acc_pspec`` row sharding applies to their state
unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregators as agg_lib
from repro.core import safeguard as sg
from repro.core import tree_utils as tu

f32 = jnp.float32

# Knob defaults shared by the defense factories below AND the campaign
# layer's ``Scenario.clip_tau/clip_beta/spectral_iters`` fields — single
# source, so the legacy Trainer path and the campaign engine run the
# same defense under the same name (mirrors attacks.ADAPTIVE_DEFAULTS).
DEFENSE_DEFAULTS = {
    "clip_tau": 1.0,        # clip radius, relative to the median deviation
    "clip_beta": 0.9,       # worker-momentum EMA coefficient
    "spectral_iters": 4,    # DnC power-iteration steps per aggregation
    "bucket_s": 2,          # bucketing meta-defense: workers per bucket
    # empirical-filter eviction multiplier (paper Appendix C.1) — single
    # source is the SafeguardConfig field default.  1.5 is the paper's
    # IID calibration; under measured heterogeneity zeta the honest
    # spread-to-median ratio grows, so the hetero campaign runs a
    # relaxed lane (DESIGN.md §13)
    "threshold_scale": sg.SafeguardConfig.threshold_scale,
}

# Decouples the bucketing permutation stream from the safeguard's noise
# consumer of the same scan-threaded step rng (ctx["rng"]).
BUCKET_SALT = 0xB0C4

_CLIP_ITERS = 3             # fixed inner clipping iterations (static)
# Static power-iteration scan length; the `spectral_iters` knob masks the
# tail so traced and concrete values run the same program (bit-identity).
# A request above the cap would silently truncate — reject it loudly.
MAX_SPECTRAL_ITERS = 16


def derive_trim(n_byz: int, m: int):
    """Per-coordinate trim count for trimmed-mean at ``b = alpha * m`` —
    THE single source (previously repeated between
    ``aggregators.make_registry`` and the campaign layer).  Accepts a
    traced ``n_byz`` (returns a traced value; only defenses that consume
    ``n_byz`` dynamically may be called with one)."""
    cap = (m - 1) // 2
    if isinstance(n_byz, (int, np.integer)):
        return min(int(n_byz), cap)
    return jnp.minimum(n_byz, cap)


@dataclasses.dataclass(frozen=True)
class Defense:
    """One defense under the unified protocol.

    ``aggregate(state, grads, ctx) -> (agg, state', info)`` — ``grads``
    is the worker-stacked pytree *after* the Byzantine rewrite; ``info``
    always has ``good``/``n_good``.  ``init_state(grads_like) -> state``
    builds the carried pytree from a parameter-shaped pytree (``None``
    for the historyless baselines).

    ``static_nbyz``: the defense consumes ``n_byz`` as a python value
    (slice/selection bounds) — program structure for the campaign
    engine, a vmap knob otherwise.  ``flat_state``: the state rows are
    ``(m, d_pad)`` flat buffers shardable by
    ``launch.sharding.flat_acc_pspec``.
    """
    name: str
    aggregate: Callable
    init_state: Optional[Callable] = None
    needs_held_batch: bool = False    # Zeno's master-side score oracle
    static_nbyz: bool = False
    flat_state: bool = False

    @property
    def stateful(self) -> bool:
        return self.init_state is not None

    @property
    def historyless(self) -> bool:
        """The paper's dividing line: a defense with no carried state can
        only see one step of gradients — derived, so it cannot drift
        from ``stateful``."""
        return not self.stateful


def final_good(state) -> Optional[jax.Array]:
    """The last good/membership mask recorded in a defense state, or
    ``None`` when the defense does not track one (stateless baselines,
    pure clipping)."""
    if state is None:
        return None
    if hasattr(state, "good"):
        return state.good
    if isinstance(state, dict):
        if "good" in state:
            return state["good"]
        if "sg" in state:
            return state["sg"].good
    return None


def _all_good_info(m: int) -> Dict[str, jax.Array]:
    return {"good": jnp.ones((m,), bool), "n_good": jnp.asarray(m, f32)}


def _masked_info(keep: jax.Array) -> Dict[str, jax.Array]:
    return {"good": keep, "n_good": keep.sum().astype(f32)}


# --------------------------------------------------------------------------
# Historyless ports (the pure functions of core.aggregators)
# --------------------------------------------------------------------------

def _stateless(name: str, fn: Callable, *, needs_scores: bool = False,
               static_nbyz: bool = False) -> Defense:
    def aggregate(state, grads, ctx):
        m = tu.tree_worker_count(grads)
        if needs_scores:
            scores = (ctx or {}).get("scores")
            if scores is None:
                raise ValueError(f"{name} needs ctx['scores'] (a held-out "
                                 "batch at the trainer level)")
            agg = fn(grads, scores=scores)
        else:
            agg = fn(grads)
        return agg, state, _all_good_info(m)

    return Defense(name, aggregate, needs_held_batch=needs_scores,
                   static_nbyz=static_nbyz)


# --------------------------------------------------------------------------
# SafeguardSGD as a Defense
# --------------------------------------------------------------------------

def make_safeguard_defense(cfg: sg.SafeguardConfig,
                           name: Optional[str] = None) -> Defense:
    """The paper's defense under the protocol: the state is the plain
    :class:`core.safeguard.SafeguardState` (flat ``(m, d_pad)``
    accumulators by default)."""
    def init_state(grads_like):
        return sg.init_state(cfg, grads_like)

    def aggregate(state, grads, ctx):
        ctx = ctx or {}
        rng = ctx.get("rng") if cfg.nu > 0 else None
        new_state, agg, info = sg.safeguard_step(
            state, grads, cfg, rng, acc_sharding=ctx.get("acc_sharding"))
        return agg, new_state, info

    return Defense(name or f"safeguard_{cfg.mode}", aggregate,
                   init_state=init_state,
                   flat_state=(cfg.engine == "flat" and not cfg.use_sketch))


def from_aggregator(a: "agg_lib.Aggregator") -> Defense:
    """Back-compat shim: wrap a legacy ``aggregators.Aggregator``."""
    return _stateless(a.name, a.fn, needs_scores=a.needs_scores)


# --------------------------------------------------------------------------
# Flat-buffer helpers shared by the new stateful defenses
# --------------------------------------------------------------------------

def _layout_of(grads) -> sg.FlatLayout:
    """Layout from a *stacked* pytree (shape metadata only — trace-time)."""
    return sg.make_layout(jax.tree.map(lambda l: l[0], grads))


def _row_norms(mat: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.maximum((mat * mat).sum(axis=1), 0.0))


def _knob(x):
    """Coerce a defense knob to an *opaque* f32 scalar BEFORE any
    arithmetic.  Two effects, both needed for the engine-vs-Trainer
    bit-identity contract: the f32 cast stops a python-float knob from
    constant-folding in float64 (``1.0 - 0.9`` is one ulp off the f32
    subtraction), and the optimization barrier stops XLA from fusing a
    *literal* knob differently (fma choices change with literal
    coefficients at some shapes) than the campaign engine's traced vmap
    lane values.  A knob that is already a tracer is already opaque —
    and ``optimization_barrier`` has no batching rule, so the barrier
    only wraps the concrete (legacy Trainer) side."""
    x = jnp.asarray(x, f32)
    if isinstance(x, jax.core.Tracer):
        return x
    return jax.lax.optimization_barrier(x)


def _clip_rounds(v: jax.Array, center: jax.Array, tau, good=None):
    """``_CLIP_ITERS`` rounds of centered clipping: pull the center toward
    the (good-masked) mean of radius-clipped deviations.  ``tau`` is
    RELATIVE to the median deviation norm — scale-free across models,
    the practical radius rule of Karimireddy et al.'s experiments."""
    m = v.shape[0]
    tau = _knob(tau)
    w_mask = jnp.ones((m,), f32) if good is None else good.astype(f32)
    denom = jnp.maximum(w_mask.sum(), 1.0)
    c = center
    for _ in range(_CLIP_ITERS):
        delta = v - c[None, :]
        nrm = _row_norms(delta)
        tau_eff = tau * jnp.median(nrm)
        w = jnp.minimum(1.0, tau_eff / jnp.maximum(nrm, 1e-12)) * w_mask
        c = c + (delta * w[:, None]).sum(axis=0) / denom
    return c


def _maybe_shard(buf, ctx):
    sharding = (ctx or {}).get("acc_sharding")
    if sharding is not None:
        buf = jax.lax.with_sharding_constraint(buf, sharding)
    return buf


# --------------------------------------------------------------------------
# Centered clipping with worker momentum
# --------------------------------------------------------------------------

def make_centered_clip(m: int, tau=DEFENSE_DEFAULTS["clip_tau"],
                       beta=DEFENSE_DEFAULTS["clip_beta"]) -> Defense:
    """[Karimireddy, He, Jaggi 2021] Per-worker momentum ``v_i <-
    (1-beta) g_i + beta v_i`` followed by iterative centered clipping of
    the momenta around the previous aggregate.  History enters twice —
    the momentum buffers and the carried center — which is exactly what
    lets it survive the variance attack no historyless rule can
    (DESIGN.md §12); nobody is evicted, influence is *bounded* instead."""
    def init_state(grads_like):
        layout = sg.make_layout(grads_like)
        return {"momentum": jnp.zeros((m, layout.d_padded), f32),
                "center": jnp.zeros((layout.d_padded,), f32)}

    def aggregate(state, grads, ctx):
        layout = _layout_of(grads)
        gflat = sg.flatten_stacked(grads, layout)
        b = _knob(beta)
        v = (1.0 - b) * gflat + b * state["momentum"]
        v = _maybe_shard(v, ctx)
        c = _clip_rounds(v, state["center"], tau)
        agg = sg.unflatten_row(c, layout)
        info = _all_good_info(m)
        info["clip_center_norm"] = jnp.sqrt((c * c).sum())
        return agg, {"momentum": v, "center": c}, info

    return Defense("centered_clip", aggregate, init_state=init_state,
                   flat_state=True)


# --------------------------------------------------------------------------
# Norm-threshold filtering with an EMA norm estimate
# --------------------------------------------------------------------------

def make_norm_filter(m: int, mult: float = 2.0,
                     ema_beta: float = 0.9) -> Defense:
    """Reject-by-magnitude (the norm-clipping/thresholding baseline of
    Sun et al. 2019 and the ByzantinePGD line of Yin et al. 2019): keep
    workers whose reported norm is within ``mult`` times an EMA of the
    *median* reported norm, mean over the kept set.  The EMA is the
    history — a one-step norm spike (burst, sign-flip at scale) is
    rejected against the remembered honest scale, not against the
    current contaminated batch."""
    def init_state(grads_like):
        return {"ema": jnp.zeros((), f32), "t": jnp.zeros((), jnp.int32),
                "good": jnp.ones((m,), bool)}

    def aggregate(state, grads, ctx):
        nrm = jnp.sqrt(tu.tree_row_sq_norms(grads))
        med = jnp.median(nrm)
        eb = _knob(ema_beta)
        ema = jnp.where(state["t"] == 0, med,
                        eb * state["ema"] + (1.0 - eb) * med)
        keep = nrm <= _knob(mult) * jnp.maximum(ema, 1e-12)
        # never aggregate an empty set: the median-norm worker stays
        keep = keep | (jnp.arange(m) == jnp.argmin(jnp.abs(nrm - med)))
        agg = tu.tree_masked_mean(grads, keep)
        info = _masked_info(keep)
        info["norm_ema"] = ema
        new_state = {"ema": ema, "t": state["t"] + 1, "good": keep}
        return agg, new_state, info

    return Defense("norm_filter", aggregate, init_state=init_state)


# --------------------------------------------------------------------------
# DnC-style spectral filtering
# --------------------------------------------------------------------------

def make_dnc(m: int, n_byz,
             iters=DEFENSE_DEFAULTS["spectral_iters"]) -> Defense:
    """Divide-and-Conquer [Shejwalkar & Houmansadr 2021]: score each
    worker by its squared projection onto the top singular direction of
    the centered ``(m, d_pad)`` gradient matrix and drop the ``n_byz``
    largest.  The power iteration is warm-started from the previous
    step's direction (the state) — colluders drifting along a stable
    direction are found in very few iterations.  ``iters`` and
    ``n_byz`` may be traced (campaign vmap knobs): the iteration runs a
    static-length masked scan (:data:`MAX_SPECTRAL_ITERS`), the drop
    count selects a sorted-score threshold with ``jnp.take``."""
    if isinstance(iters, (int, np.integer)) and iters > MAX_SPECTRAL_ITERS:
        raise ValueError(
            f"spectral_iters={iters} exceeds MAX_SPECTRAL_ITERS="
            f"{MAX_SPECTRAL_ITERS} (the static scan length) and would "
            "silently truncate")

    def init_state(grads_like):
        layout = sg.make_layout(grads_like)
        v0 = jax.random.normal(jax.random.PRNGKey(0), (layout.d_padded,),
                               f32)
        return {"v": v0 / jnp.sqrt((v0 * v0).sum()),
                "good": jnp.ones((m,), bool)}

    def aggregate(state, grads, ctx):
        layout = _layout_of(grads)
        gflat = sg.flatten_stacked(grads, layout)
        centered = gflat - gflat.mean(axis=0, keepdims=True)

        def power_step(v, i):
            w = centered.T @ (centered @ v)          # O(m d) per iteration
            w = w / jnp.maximum(jnp.sqrt((w * w).sum()), 1e-12)
            return jnp.where(i < iters, w, v), None

        v, _ = jax.lax.scan(power_step, state["v"],
                            jnp.arange(MAX_SPECTRAL_ITERS))
        scores = (centered @ v) ** 2
        k = jnp.clip(jnp.asarray(n_byz, jnp.int32), 0, m - 1)
        thresh = jnp.take(jnp.sort(scores), m - 1 - k)
        keep = scores <= thresh
        agg = tu.tree_masked_mean(grads, keep)
        info = _masked_info(keep)
        info["spectral_scores"] = scores
        return agg, {"v": v, "good": keep}, info

    return Defense("dnc", aggregate, init_state=init_state,
                   flat_state=True)


# --------------------------------------------------------------------------
# Safeguard + centered clipping composition
# --------------------------------------------------------------------------

def make_safeguard_cclip(cfg: sg.SafeguardConfig,
                         tau=DEFENSE_DEFAULTS["clip_tau"],
                         beta=DEFENSE_DEFAULTS["clip_beta"]) -> Defense:
    """Composition: the safeguard's windowed filter decides *membership*
    (permanent eviction of drifting accumulators), centered clipping
    bounds the *per-step influence* of whoever remains — the two
    failure modes the components each leave open.  Publishes the full
    safeguard feedback (thresholds, distances), so adaptive attacks see
    the same public surface as against the plain safeguard."""
    if cfg.engine != "flat" or cfg.use_sketch:
        raise ValueError("safeguard_cclip requires the flat engine")

    def init_state(grads_like):
        sg_state = sg.init_state(cfg, grads_like)
        d_pad = sg_state.layout.d_padded
        return {"sg": sg_state,
                "momentum": jnp.zeros((cfg.m, d_pad), f32),
                "center": jnp.zeros((d_pad,), f32)}

    def aggregate(state, grads, ctx):
        ctx = ctx or {}
        rng = ctx.get("rng") if cfg.nu > 0 else None
        sg_state, _sg_agg, info = sg.safeguard_step(
            state["sg"], grads, cfg, rng,
            acc_sharding=ctx.get("acc_sharding"))
        layout = sg_state.layout
        gflat = sg.flatten_stacked(grads, layout)
        b = _knob(beta)
        v = (1.0 - b) * gflat + b * state["momentum"]
        v = _maybe_shard(v, ctx)
        c = _clip_rounds(v, state["center"], tau, good=info["good"])
        agg = sg.unflatten_row(c, layout)
        new_state = {"sg": sg_state, "momentum": v, "center": c}
        return agg, new_state, info

    return Defense("safeguard_cclip", aggregate, init_state=init_state,
                   flat_state=True)


# --------------------------------------------------------------------------
# Bucketing as a meta-defense
# --------------------------------------------------------------------------

def derive_bucket_nbyz(n_byz: int, s: int) -> int:
    """Byzantine budget for the *inner* aggregator after s-bucketing:
    each Byzantine worker contaminates at most one bucket, so at most
    ``ceil(b / s)`` bucket means are corrupt [Karimireddy, He & Jaggi
    2022, Lemma 1].  NOT capped — if the wrapped rule cannot tolerate
    this many corrupt inputs the combination is unsound, and the
    registry must omit it rather than silently understate the budget."""
    return -(-int(n_byz) // s)


def bucketing_krum_feasible(m: int, n_byz: int, s: int) -> bool:
    """Can inner Krum tolerate ``ceil(n_byz / s)`` corrupt bucket means
    on ``m / s`` buckets (Krum needs m > b + 2)?  THE single source for
    the registry's registration gate and the Scenario construction-time
    check — one recalibration site, no drift."""
    if s < 1 or m % s or m // s < 3:
        return False
    return derive_bucket_nbyz(n_byz, s) <= m // s - 3


def make_bucketing(inner: Defense, m: int, s: int,
                   name: Optional[str] = None) -> Defense:
    """[Karimireddy, He & Jaggi 2022] s-bucket random averaging before
    ANY wrapped aggregator: each step draws a fresh worker permutation
    from the scan-threaded rng (``ctx["rng"]``, salted), averages
    consecutive groups of ``s`` permuted workers into ``m/s`` bucket
    means, and hands those to the wrapped defense as if they were
    workers.  Averaging s random workers shrinks inter-"worker"
    heterogeneity by ~1/s, which is exactly what stops selection-style
    rules (Krum, medians) from locking onto one skewed shard under
    non-IID data (DESIGN.md §13) — while Byzantine influence stays
    bounded (a colluder corrupts at most its own bucket).

    The wrapped defense runs at ``m_inner = m / s``; its state (if any)
    is bucket-shaped and threads through unchanged.  Bucket-level
    ``good`` decisions are mapped back through the permutation to the
    ``(m,)`` worker surface the trainer and the adaptive attacks
    observe; bucket-level score/distance arrays are dropped (their
    worker axis is the wrong size for the feedback protocol), scalar
    diagnostics pass through.
    """
    if s < 1:
        raise ValueError(f"bucketing needs s >= 1, got {s}")
    if m % s:
        raise ValueError(f"bucketing: m={m} not divisible by bucket size "
                         f"s={s}")
    if inner.needs_held_batch:
        raise ValueError("bucketing cannot wrap a held-batch defense "
                         f"({inner.name}): its score oracle is per-worker, "
                         "not per-bucket")
    n_buckets = m // s

    def aggregate(state, grads, ctx):
        rng = (ctx or {}).get("rng")
        if rng is None:
            raise ValueError("bucketing needs ctx['rng'] (the "
                             "scan-threaded step rng)")
        perm = jax.random.permutation(jax.random.fold_in(rng, BUCKET_SALT),
                                      m)

        def bucketize(leaf):
            p = jnp.take(leaf, perm, axis=0)
            p = p.reshape((n_buckets, s) + leaf.shape[1:])
            return p.astype(f32).mean(axis=1).astype(leaf.dtype)

        buckets = jax.tree.map(bucketize, grads)
        agg, new_state, binfo = inner.aggregate(state, buckets, ctx)
        # bucket decision -> worker surface: a worker is good iff its
        # bucket survived this step's inner aggregation
        good = jnp.zeros((m,), bool).at[perm].set(
            jnp.repeat(binfo["good"], s))
        info = _masked_info(good)
        info["bucket_good"] = binfo["good"]
        for k, v in binfo.items():
            if k in ("good", "n_good") or k.startswith("threshold"):
                continue                       # wrong worker axis / surface
            if getattr(v, "ndim", None) == 0:
                info[k] = v
        return agg, new_state, info

    # flat_state stays False even for a flat-buffer inner: the inner
    # state has m/s rows, not the m worker rows the flat_acc_pspec
    # sharding contract promises (launch/specs would otherwise pin an
    # m-row spec onto a bucket-shaped buffer)
    return Defense(name or f"bucketing_{inner.name}", aggregate,
                   init_state=inner.init_state,
                   static_nbyz=inner.static_nbyz)


def _bucketing_static_nbyz_placeholder(name: str) -> Defense:
    """Registry slot for a bucketing-wrapped static-n_byz defense when the
    registry was built with a *traced* n_byz: the bucket Byzantine
    budget (``derive_bucket_nbyz``) is python slice structure, so such
    an entry can exist for name lookups but must never aggregate."""
    def aggregate(state, grads, ctx):
        raise ValueError(f"{name} consumes n_byz statically; build the "
                         "registry with a concrete n_byz to use it")
    return Defense(name, aggregate, static_nbyz=True)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

def make_registry(m: int, n_byz, *, T0: int = 20, T1: int = 120,
                  threshold_floor=0.1, reset_period: int = 0,
                  use_sketch: bool = False,
                  clip_tau=DEFENSE_DEFAULTS["clip_tau"],
                  clip_beta=DEFENSE_DEFAULTS["clip_beta"],
                  spectral_iters=DEFENSE_DEFAULTS["spectral_iters"],
                  bucket_s: int = DEFENSE_DEFAULTS["bucket_s"],
                  threshold_scale=DEFENSE_DEFAULTS["threshold_scale"],
                  norm_mult: float = 2.0,
                  norm_ema_beta: float = 0.9) -> Dict[str, Defense]:
    """Every defense, parameterized the way the paper's protocol runs
    them (``b = alpha * m``; safeguard windows/thresholds as given).

    ``threshold_floor``, ``clip_tau``, ``clip_beta``, ``spectral_iters``
    and — for the non-``static_nbyz`` entries — ``n_byz`` may be traced
    scalars (campaign vmap knobs): registry construction never calls a
    defense, and the knobs only feed arithmetic inside ``aggregate``.
    """
    trim = derive_trim(n_byz, m)

    def sg_cfg(mode):
        return sg.SafeguardConfig(m=m, T0=T0, T1=T1, mode=mode,
                                  threshold_floor=threshold_floor,
                                  threshold_scale=threshold_scale,
                                  reset_period=reset_period,
                                  use_sketch=use_sketch)

    reg = {
        "mean": _stateless("mean", agg_lib.mean),
        "coord_median": _stateless("coord_median",
                                   agg_lib.coordinate_median),
        "trimmed_mean": _stateless(
            "trimmed_mean",
            functools.partial(agg_lib.trimmed_mean, trim=trim),
            static_nbyz=True),
        "geo_median": _stateless("geo_median", agg_lib.geometric_medoid),
        "weiszfeld": _stateless("weiszfeld", agg_lib.geometric_median),
        "krum": _stateless(
            "krum", functools.partial(agg_lib.krum, n_byz=n_byz),
            static_nbyz=True),
        "zeno": _stateless(
            "zeno", functools.partial(agg_lib.zeno, n_byz=n_byz),
            needs_scores=True, static_nbyz=True),
        "safeguard_single": make_safeguard_defense(sg_cfg("single"),
                                                   "safeguard_single"),
        "safeguard_double": make_safeguard_defense(sg_cfg("double"),
                                                   "safeguard_double"),
        "centered_clip": make_centered_clip(m, tau=clip_tau,
                                            beta=clip_beta),
        "norm_filter": make_norm_filter(m, mult=norm_mult,
                                        ema_beta=norm_ema_beta),
        "dnc": make_dnc(m, n_byz, iters=spectral_iters),
    }
    if not use_sketch:
        # the composition needs the flat accumulators (its momentum shares
        # their layout) — a sketched registry simply omits it rather than
        # refusing to build the twelve defenses that work fine
        reg["safeguard_cclip"] = make_safeguard_cclip(sg_cfg("double"),
                                                      tau=clip_tau,
                                                      beta=clip_beta)
    # bucketing meta-defense (DESIGN.md §13): registered whenever the
    # bucket shapes work out (m divisible, enough buckets for the inner
    # rule); an incompatible population simply omits the entries, like
    # the sketched registry omits safeguard_cclip
    if bucket_s >= 1 and m % bucket_s == 0 and (m // bucket_s) >= 3:
        nb = m // bucket_s
        if not isinstance(n_byz, (int, np.integer)):
            # traced n_byz (a campaign knob for some OTHER defense in the
            # same registry build): keep the name resolvable, refuse use
            reg["bucketing_krum"] = _bucketing_static_nbyz_placeholder(
                "bucketing_krum")
        elif bucketing_krum_feasible(m, n_byz, bucket_s):
            # only register when inner Krum can actually tolerate
            # ceil(b/s) corrupt bucket means — an unsound combination is
            # omitted, never silently weakened
            inner_krum = _stateless(
                "krum", functools.partial(
                    agg_lib.krum,
                    n_byz=derive_bucket_nbyz(n_byz, bucket_s)),
                static_nbyz=True)
            reg["bucketing_krum"] = make_bucketing(inner_krum, m, bucket_s)
        reg["bucketing_cclip"] = make_bucketing(
            make_centered_clip(nb, tau=clip_tau, beta=clip_beta),
            m, bucket_s, name="bucketing_cclip")
    return reg


def static_nbyz_names() -> frozenset:
    """Defense names that consume ``n_byz`` as program structure — the
    campaign engine keys its batch groups on this (single source; the
    frozenset previously hard-coded in ``campaign.engine``).  The probe
    population (m=8, b=1) is the smallest where every registry entry —
    including bucketing_krum's feasibility gate — registers."""
    return frozenset(name for name, d in make_registry(8, 1).items()
                     if d.static_nbyz)
