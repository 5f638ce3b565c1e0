"""Typed metric schema / registry — layer 1 of the flight recorder
(DESIGN.md §15).

Every per-step statistic this repo emits crosses one of two surfaces:

  * the **metric surface** — the dict ``train.trainer.make_train_step``
    returns each step (and ``scan_trial`` stacks into traces);
  * the **info surface** — the dict every ``Defense.aggregate``
    publishes (the public outputs adaptive attacks observe and the
    trainer re-traces).

Before this layer both were untyped: a defense could rename a key, emit
an ``(m,)`` array where a scalar was expected, or silently change dtype,
and nothing would notice until a campaign JSONL stopped lining up with
an older one.  The registry below gives each name a :class:`MetricSpec`
(canonical dtype, shape class, source, guard-window tag) and the
``validate_*`` entry points enforce it **at trace time** — shapes and
dtypes of jax tracers are static, so validation runs once per program
trace and costs nothing per step.

PR 10 adds a third surface:

  * the **tap surface** — the bounded per-window summary
    ``scan_trial(tap_every=K)`` streams out of the running scan through
    ``jax.experimental.io_callback`` (``repro.obs.live``); every tap key
    is a *scalar* (the payload must stay bounded regardless of model
    size), and its ``agg`` field records how the window of per-step
    values is reduced to one number (``mean`` over the window or
    ``last`` value), so a heartbeat line is interpretable without the
    producing program.

Shape classes:

  ``scalar``       shape ``()``
  ``per_worker``   shape ``(m,)`` — one entry per simulated worker row
  ``per_window``   shape ``()``, tagged with the safeguard guard window
                   (``B`` = inner/T0, ``A`` = outer/T1) the statistic
                   belongs to; per-window *vectors* (``dist_to_med_B``)
                   are ``per_worker`` with a window tag
  ``per_bucket``   1-D with length dividing ``m`` — the bucketing
                   meta-defense's bucket axis (``m / bucket_s`` rows)

Dtype validation is by *kind* (floating / integer / bool): the canonical
dtype in the spec is what the CPU protocol produces (and what the
``.npz`` trace sidecars store), but an at-scale bf16 loss is the same
metric.  A shape-class violation or an unregistered name raises
:class:`SchemaError` naming the key — extend with
:func:`register_metric` (e.g. for a custom ``so_probe``) instead of
silencing."""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional

import numpy as np

SCALAR = "scalar"
PER_WORKER = "per_worker"
PER_WINDOW = "per_window"
PER_BUCKET = "per_bucket"
SHAPE_CLASSES = (SCALAR, PER_WORKER, PER_WINDOW, PER_BUCKET)

# surfaces a spec may be registered on
METRIC_SURFACE = "metrics"
INFO_SURFACE = "info"
TAP_SURFACE = "tap"

# window-reduction modes a tap key may declare
TAP_AGGS = ("mean", "last", "host")


class SchemaError(ValueError):
    """A metric/info dict violated the typed schema (unknown name, wrong
    shape class, wrong dtype kind)."""


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """One registered statistic.

    ``dtype`` is the canonical dtype name (validation is by kind);
    ``window`` tags the safeguard guard window (``"B"``/``"A"``) for
    per-window statistics; ``source`` names the layer that emits it."""
    name: str
    dtype: str                      # canonical: float32 | int32 | bool
    shape_class: str                # one of SHAPE_CLASSES
    source: str                     # trainer | defense | probe | attack | model
    description: str = ""
    window: Optional[str] = None    # "B" | "A" for guard-window stats
    agg: Optional[str] = None       # tap surface: mean | last | host

    def __post_init__(self):
        if self.shape_class not in SHAPE_CLASSES:
            raise ValueError(f"unknown shape class {self.shape_class!r} "
                             f"(one of {SHAPE_CLASSES})")
        if self.agg is not None and self.agg not in TAP_AGGS:
            raise ValueError(f"unknown tap agg {self.agg!r} "
                             f"(one of {TAP_AGGS})")


def _spec_table(specs: Iterable[MetricSpec]) -> Dict[str, MetricSpec]:
    return {s.name: s for s in specs}


# --------------------------------------------------------------------------
# The info surface: every key any Defense.aggregate may publish
# --------------------------------------------------------------------------

INFO: Dict[str, MetricSpec] = _spec_table([
    MetricSpec("good", "bool", PER_WORKER, "defense",
               "membership mask aggregated over this step"),
    MetricSpec("n_good", "float32", SCALAR, "defense",
               "live good-set size"),
    MetricSpec("med_B", "int32", PER_WINDOW, "defense",
               "concentration-median worker index, inner window",
               window="B"),
    MetricSpec("med_A", "int32", PER_WINDOW, "defense",
               "concentration-median worker index, outer window",
               window="A"),
    MetricSpec("threshold_B", "float32", PER_WINDOW, "defense",
               "live eviction threshold, inner (T0) guard", window="B"),
    MetricSpec("threshold_A", "float32", PER_WINDOW, "defense",
               "live eviction threshold, outer (T1) guard", window="A"),
    MetricSpec("dist_to_med_B", "float32", PER_WORKER, "defense",
               "per-worker accumulator distance to the inner-window "
               "median", window="B"),
    MetricSpec("dist_to_med_A", "float32", PER_WORKER, "defense",
               "per-worker accumulator distance to the outer-window "
               "median", window="A"),
    MetricSpec("scores_B", "float32", PER_WORKER, "defense",
               "Appendix C.1 concentration scores, inner window",
               window="B"),
    MetricSpec("newly_evicted", "bool", PER_WORKER, "defense",
               "workers evicted by exactly this step's filter"),
    MetricSpec("restored", "bool", PER_WORKER, "defense",
               "workers readmitted by this step's periodic reset"),
    MetricSpec("clip_center_norm", "float32", SCALAR, "defense",
               "centered-clipping aggregate norm"),
    MetricSpec("norm_ema", "float32", SCALAR, "defense",
               "norm_filter's EMA of the median reported norm"),
    MetricSpec("spectral_scores", "float32", PER_WORKER, "defense",
               "DnC squared projection onto the top singular direction"),
    MetricSpec("bucket_good", "bool", PER_BUCKET, "defense",
               "bucketing meta-defense: per-bucket inner decision"),
])

# --------------------------------------------------------------------------
# The metric surface: every key make_train_step may emit
# --------------------------------------------------------------------------

METRICS: Dict[str, MetricSpec] = _spec_table([
    MetricSpec("loss", "float32", SCALAR, "trainer",
               "mean per-worker training loss (attacked rows included)"),
    MetricSpec("honest_loss", "float32", SCALAR, "trainer",
               "mean training loss over honest workers"),
    MetricSpec("zeta_sq", "float32", SCALAR, "trainer",
               "measured gradient dissimilarity over the ground-truth "
               "honest set (DESIGN.md §13)"),
    MetricSpec("zeta_good_sq", "float32", SCALAR, "trainer",
               "measured dissimilarity over the defense's live good set"),
    MetricSpec("n_good", "float32", SCALAR, "trainer",
               "live good-set size (re-traced from the defense info)"),
    MetricSpec("caught_byz", "int32", SCALAR, "trainer",
               "Byzantine workers outside the current good set"),
    MetricSpec("evicted_honest", "int32", SCALAR, "trainer",
               "honest workers outside the current good set"),
    MetricSpec("restored", "int32", SCALAR, "trainer",
               "workers readmitted by this step's periodic reset"),
    MetricSpec("good", "bool", PER_WORKER, "trainer",
               "post-decision membership mask (the event layer derives "
               "evictions/restorations from its transitions)"),
    MetricSpec("dist_to_med_B", "float32", PER_WORKER, "trainer",
               "per-worker distance to the inner-window median",
               window="B"),
    MetricSpec("dist_to_med_A", "float32", PER_WORKER, "trainer",
               "per-worker distance to the outer-window median",
               window="A"),
    MetricSpec("threshold_B", "float32", PER_WINDOW, "trainer",
               "live eviction threshold, inner (T0) guard", window="B"),
    MetricSpec("threshold_A", "float32", PER_WINDOW, "trainer",
               "live eviction threshold, outer (T1) guard", window="A"),
    MetricSpec("grad_norm", "float32", SCALAR, "trainer",
               "norm of the aggregated (post-defense) direction"),
    MetricSpec("expert_rows_max", "float32", PER_WORKER, "model",
               "largest number of assignments a held routed expert "
               "computed in this step's forward, over layers"),
    MetricSpec("expert_rows_mean", "float32", PER_WORKER, "model",
               "mean number of assignments per held routed expert and "
               "layer in this step's forward"),
    MetricSpec("escape_on", "float32", SCALAR, "trainer",
               "sgd_escape perturbation gate (1 = noise injected)"),
    MetricSpec("attack_level", "float32", SCALAR, "attack",
               "adaptive-attack controller level consumed by this "
               "step's act() (aggression / z / scale / eps / boost)"),
    MetricSpec("true_grad_norm", "float32", SCALAR, "probe",
               "planted-saddle analytic gradient norm (DESIGN.md §14)"),
    MetricSpec("min_eig_proxy", "float32", SCALAR, "probe",
               "Rayleigh min-eigenvalue proxy along planted directions"),
    MetricSpec("escaped", "float32", SCALAR, "probe",
               "analytic escape predicate of the current iterate"),
])

# --------------------------------------------------------------------------
# The tap surface: the bounded per-window summary scan_trial streams out
# of a running scan (tap_every=K).  Every key is a scalar; ``agg`` says
# how the K-step window reduces to it (``mean`` / ``last``), or ``host``
# for keys the host-side collector stamps on (never traced).
# --------------------------------------------------------------------------

TAP: Dict[str, MetricSpec] = _spec_table([
    MetricSpec("step", "int32", SCALAR, "trainer",
               "global step count at the window's end", agg="last"),
    MetricSpec("loss", "float32", SCALAR, "trainer",
               "window-mean per-worker training loss", agg="mean"),
    MetricSpec("honest_loss", "float32", SCALAR, "trainer",
               "window-mean honest training loss", agg="mean"),
    MetricSpec("grad_norm", "float32", SCALAR, "trainer",
               "aggregated-direction norm at the window's last step",
               agg="last"),
    MetricSpec("n_good", "float32", SCALAR, "trainer",
               "live good-set size (popcount) at the window's last step",
               agg="last"),
    MetricSpec("caught_byz", "int32", SCALAR, "trainer",
               "Byzantine workers outside the good set, window end",
               agg="last"),
    MetricSpec("evicted_honest", "int32", SCALAR, "trainer",
               "honest workers outside the good set, window end",
               agg="last"),
    MetricSpec("threshold_B", "float32", SCALAR, "trainer",
               "live inner (T0) eviction threshold, window end",
               window="B", agg="last"),
    MetricSpec("threshold_A", "float32", SCALAR, "trainer",
               "live outer (T1) eviction threshold, window end",
               window="A", agg="last"),
    MetricSpec("min_eig_proxy", "float32", SCALAR, "probe",
               "Rayleigh min-eigenvalue proxy, window end", agg="last"),
    MetricSpec("escape_on", "float32", SCALAR, "trainer",
               "sgd_escape gate at the window's last step", agg="last"),
    MetricSpec("attack_level", "float32", SCALAR, "attack",
               "adaptive-attack controller level, window end", agg="last"),
    MetricSpec("lane", "int32", SCALAR, "trainer",
               "vmap lane index inside the emitting batch group (threaded "
               "through the device payload: vmapped callbacks fire "
               "per-lane with no other lane identity)", agg="last"),
    MetricSpec("step_rate", "float32", SCALAR, "trainer",
               "host-measured steps/s since the lane's previous "
               "heartbeat", agg="host"),
    MetricSpec("t_wall", "float32", SCALAR, "trainer",
               "host wall-clock seconds since the collector attached",
               agg="host"),
])

# tap keys that cross the device->host boundary (everything not host-
# stamped), in a fixed order — the io_callback payload is this tuple
DEVICE_TAP_KEYS = tuple(
    n for n, s in TAP.items() if s.agg != "host")

_SURFACES = {METRIC_SURFACE: METRICS, INFO_SURFACE: INFO, TAP_SURFACE: TAP}


def register_metric(spec: MetricSpec, surface: str = METRIC_SURFACE,
                    overwrite: bool = False) -> MetricSpec:
    """Register a new statistic (e.g. a custom ``so_probe`` output).
    Refuses to silently redefine an existing name."""
    table = _SURFACES[surface]
    if spec.name in table and not overwrite:
        raise SchemaError(f"metric {spec.name!r} already registered on the "
                          f"{surface} surface as {table[spec.name]}; pass "
                          "overwrite=True to redefine")
    table[spec.name] = spec
    return spec


# --------------------------------------------------------------------------
# Validation (trace-time: shapes/dtypes of tracers are static)
# --------------------------------------------------------------------------

_KINDS = {"f": "floating", "i": "integer", "u": "integer", "b": "bool"}


def _kind(dtype) -> str:
    dt = np.dtype(dtype)
    # ml_dtypes extension floats (bfloat16, float8_*) register with
    # numpy as kind "V" (void); classify them by name
    if dt.kind == "V" and "float" in dt.name:
        return "floating"
    return _KINDS.get(dt.kind, dt.kind)


def _check(name: str, value, spec: MetricSpec, m: int, where: str) -> None:
    # NB: don't use getattr(value, ..., np.asarray(value)...) — the
    # fallback would be evaluated eagerly, and np.asarray on a jax
    # tracer raises TracerArrayConversionError
    shape = (tuple(value.shape) if hasattr(value, "shape")
             else tuple(np.shape(value)))
    dtype = (value.dtype if hasattr(value, "dtype")
             else np.asarray(value).dtype)
    if spec.shape_class in (SCALAR, PER_WINDOW):
        ok = shape == ()
        want = "()"
    elif spec.shape_class == PER_WORKER:
        ok = shape == (m,)
        want = f"({m},)"
    else:                                           # PER_BUCKET
        ok = len(shape) == 1 and shape[0] >= 1 and m % shape[0] == 0
        want = f"(m/s,) with m={m}"
    if not ok:
        raise SchemaError(
            f"{where}: {name!r} has shape {shape}, but its schema class "
            f"is {spec.shape_class!r} (expects {want})")
    if _kind(dtype) != _kind(spec.dtype):
        raise SchemaError(
            f"{where}: {name!r} has dtype {np.dtype(dtype).name} "
            f"({_kind(dtype)}), but its schema dtype is {spec.dtype} "
            f"({_kind(spec.dtype)})")


def _validate(d: Dict, m: int, table: Dict[str, MetricSpec], where: str
              ) -> None:
    for name, value in d.items():
        spec = table.get(name)
        if spec is None:
            kind = ("info" if table is INFO
                    else "tap" if table is TAP else "metric")
            raise SchemaError(
                f"{where}: {name!r} is not a registered "
                f"{kind} name — add it "
                "to repro.obs.schema (register_metric) so traces stay "
                f"comparable across campaigns; registered: "
                f"{sorted(table)}")
        _check(name, value, spec, m, where)


def validate_metrics(metrics: Dict, m: int, where: str = "train_step"
                     ) -> Dict:
    """Validate a trainer step-metric dict against the schema; returns
    the dict unchanged (chainable).  Call at trace time."""
    _validate(metrics, m, METRICS, where)
    return metrics


def validate_info(info: Dict, m: int, where: str = "defense") -> Dict:
    """Validate a ``Defense.aggregate`` info dict against the schema;
    returns the dict unchanged (chainable)."""
    _validate(info, m, INFO, where)
    return info


def validate_tap(payload: Dict, where: str = "tap") -> Dict:
    """Validate a tap payload (the per-window summary ``scan_trial``
    streams through ``io_callback``) against the tap surface; returns
    the dict unchanged.  Tap keys are all scalars, so ``m`` is moot."""
    _validate(payload, 0, TAP, where)
    return payload


def spec_of(name: str, surface: str = METRIC_SURFACE) -> MetricSpec:
    table = _SURFACES[surface]
    if name not in table:
        raise SchemaError(f"unknown {surface} name {name!r}")
    return table[name]
