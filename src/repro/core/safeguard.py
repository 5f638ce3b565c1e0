"""SafeguardSGD (Allen-Zhu, Ebrahimian, Li, Alistarh — ICLR 2021).

Implements the paper's Algorithm 1 (double safe guard) and Algorithm 2
(single safe guard) as a pure-JAX aggregation layer:

  * per-worker accumulators ``A_i`` (long window ``T1``) and ``B_i`` (short
    window ``T0``) of the reported gradients, each divided by the number of
    currently-good workers, reset at every multiple of the window length;
  * a *concentration median* ``A_med``: either the paper's theoretical rule
    (any good worker whose accumulator is within threshold of a strict
    majority) or the empirical rule of Appendix C.1 (argmin over workers of
    the ``ceil(m/2 + 1)``-th smallest pairwise distance, with an automatic
    threshold ``scale * max(score, floor)``);
  * permanent eviction of any worker farther than the threshold from the
    median — within the current window; an optional periodic *full reset*
    (Section 5) restores evicted workers every ``reset_period`` steps,
    which tolerates transient failures and bounded ID relabeling;
  * the SGD direction: mean of the reported gradients over currently-good
    workers, optionally plus the isotropic Gaussian perturbation
    ``xi ~ N(0, nu^2 I)`` used by the theory to escape saddle points.

Three state representations are provided (DESIGN.md §6):

  * **flat** (default): the accumulators are single ``(m, d_pad)``
    matrices in one fixed ``tree_flatten`` layout (:class:`FlatLayout`,
    computed once at :func:`init_state`; :func:`unflatten_row` recovers a
    parameter pytree for diagnostics).  The accumulate-and-reset update is
    one fused in-place chain of column-slice adds into the buffer (the
    reset ``where`` is the only copy; every scatter after it updates in
    place), and the pairwise-distance pass runs on the whole buffer at
    once — the ``safeguard_filter`` Pallas Gram kernel
    (``backend="pallas"``, interpret mode on CPU with the package's
    ``ref.py`` as numerics oracle), the oracle's fused f32 multiply-reduce
    (``backend="xla"``, the choice under a sharded mesh, DESIGN.md §3), or
    the fully fused accumulate+distance kernel streaming each d-tile
    through VMEM exactly once (``backend="pallas_fused"``, the TPU hot
    path — it needs the gradients flattened to one matrix first, which is
    why it is not the CPU default);
  * **stacked** (paper-faithful reference): full stacked gradient pytrees,
    pairwise distances leaf-by-leaf via ``core.tree_utils.tree_gram``.
    Kept as the numerics oracle and for model-axis-sharded giants whose
    flat buffer would not fit a single row on one device;
  * **sketched** (beyond paper, DESIGN.md §3): accumulate CountSketch
    projections, ``O(m * r * k)`` state, identical filter decisions up to
    JL distortion.

Everything is ``jit``-safe: masks instead of dynamic shapes, ``where``
instead of branches; the flat layout is static pytree metadata.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import tree_utils as tu
from repro.core import sketch as sk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------
# Flat buffer layout
# --------------------------------------------------------------------------

_LANE = 128           # TPU lane multiple (feature axis)
_BLOCK_D = 512        # smallest d-tile of the Pallas kernel
_MAX_TILE = 32768     # largest d-tile the layout pads for


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static description of the one-time ``tree_flatten`` of the model's
    gradient pytree into a single ``(m_pad, d_pad)`` row-per-worker buffer.

    Hashable (it rides along as pytree *metadata* of
    :class:`SafeguardState`), computed exactly once at :func:`init_state`.
    ``offsets[i]:offsets[i]+sizes[i]`` is leaf ``i``'s column slice.
    """
    treedef: Any                      # jax PyTreeDef of the param pytree
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    d: int                            # true model dimension
    d_padded: int                     # d rounded up to a kernel-tile multiple


def _pad_multiple(d: int) -> int:
    """Feature-axis padding multiple: the lane width below one tile, else
    the largest power-of-two d-tile up to ``_MAX_TILE`` that costs at most
    ``d / 4096`` zero columns.  Large tiles keep the kernel's grid short:
    fewer grid steps and fewer per-tile f32 partial Grams to sum."""
    if d < _BLOCK_D:
        return _LANE
    tile = _BLOCK_D
    while 2 * tile <= min(_MAX_TILE, d // 4096):
        tile *= 2
    return tile


def make_layout(grads_like) -> FlatLayout:
    """``grads_like``: a parameter pytree (NOT worker-stacked).  The feature
    axis is padded to a kernel-tile multiple (zeros never change
    distances), so every downstream op is tile-aligned with no per-step
    re-padding."""
    leaves, treedef = jax.tree_util.tree_flatten(grads_like)
    if not leaves:
        raise ValueError("empty gradient pytree")
    shapes, dtypes, offsets, sizes = [], [], [], []
    off = 0
    for leaf in leaves:
        size = 1
        for s in leaf.shape:
            size *= int(s)
        shapes.append(tuple(int(s) for s in leaf.shape))
        dtypes.append(str(jnp.dtype(leaf.dtype)))
        offsets.append(off)
        sizes.append(size)
        off += size
    d = off
    d_padded = d + (-d) % _pad_multiple(d)
    return FlatLayout(treedef=treedef, shapes=tuple(shapes),
                      dtypes=tuple(dtypes), offsets=tuple(offsets),
                      sizes=tuple(sizes), d=d, d_padded=d_padded)


def flatten_stacked(grads, layout: FlatLayout) -> jax.Array:
    """Worker-stacked pytree (leaves ``(m, ...)``) -> ``(m, d_pad)`` f32
    matrix in the layout's column order, zero-padded feature columns."""
    leaves = jax.tree_util.tree_leaves(grads)
    m = leaves[0].shape[0]
    parts = [leaf.astype(jnp.float32).reshape(m, -1) for leaf in leaves]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if flat.shape[1] != layout.d:
        raise ValueError(
            f"gradient pytree has d={flat.shape[1]}, layout has {layout.d}")
    if layout.d_padded != layout.d:
        flat = jnp.pad(flat, ((0, 0), (0, layout.d_padded - layout.d)))
    return flat


def unflatten_row(row: jax.Array, layout: FlatLayout):
    """Inverse of :func:`flatten_stacked` for one worker row ``(d_pad,)``:
    recovers the parameter-pytree view of an accumulator (diagnostics)."""
    out = []
    for shape, dt, off, size in zip(layout.shapes, layout.dtypes,
                                    layout.offsets, layout.sizes):
        out.append(row[off:off + size].reshape(shape).astype(dt))
    return jax.tree_util.tree_unflatten(layout.treedef, out)


# --------------------------------------------------------------------------
# Config
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SafeguardConfig:
    """Hyper-parameters of the safeguard filter.

    ``mode``:
      * ``"double"`` — Algorithm 1 (windows ``T0 <= T1``, thresholds
        ``thresh0 <= thresh1``);
      * ``"single"`` — Algorithm 2 (only the ``B``/short guard is active).
    ``rule``:
      * ``"empirical"`` — Appendix C.1 scoring + auto threshold;
      * ``"theoretical"`` — fixed thresholds ``thresh0/1 = Theta(sqrt(T))``,
        majority-ball median, eviction at ``2 * thresh``.
    ``engine``:
      * ``"flat"`` — flat-buffer streaming accumulators (default);
      * ``"stacked"`` — paper-faithful stacked-pytree reference.
    ``backend`` (flat engine only):
      * ``"pallas"`` — in-place scatter accumulate + the blocked Pallas
        Gram kernel (interpret mode off-TPU);
      * ``"pallas_fused"`` — single streamed accumulate+distance kernel
        (flattens the gradients to one matrix per step; the TPU hot path);
        requires f32 accumulators;
      * ``"xla"`` — in-place scatter accumulate + the oracle's fused f32
        multiply-reduce (``kernels/safeguard_filter/ref.py``);
        use under a sharded mesh where a single-device kernel cannot be
        partitioned (DESIGN.md §3).
    """
    m: int                      # number of workers
    T0: int = 100               # short window length (steps)
    T1: int = 600               # long window length (steps)
    mode: str = "double"        # "double" | "single"
    rule: str = "empirical"     # "empirical" | "theoretical"
    # theoretical rule: fixed thresholds (paper: 8 * sqrt(T log(16mT/p)))
    thresh0: float = 0.0
    thresh1: float = 0.0
    # empirical rule (Appendix C.1)
    threshold_scale: float = 1.5
    threshold_floor: float = 5.0
    # Gaussian perturbation xi ~ N(0, nu^2 I); nu = 0 disables (paper C.1)
    nu: float = 0.0
    # Section 5: restore all workers every ``reset_period`` steps (0 = never)
    reset_period: int = 0
    # aggregate over the pre-filter good set (paper Alg 1 line 12 uses
    # good_t, i.e. eviction takes effect next step)
    aggregate_prefilter: bool = True
    # sketched safeguard (beyond paper)
    use_sketch: bool = False
    sketch_k: int = 2048
    sketch_reps: int = 4
    sketch_seed: int = 0
    # exact accumulators: state representation + distance implementation
    engine: str = "flat"        # "flat" | "stacked"
    backend: str = "pallas"     # "pallas" | "xla"
    acc_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.mode not in ("double", "single"):
            raise ValueError(f"bad mode {self.mode!r}")
        if self.rule not in ("empirical", "theoretical"):
            raise ValueError(f"bad rule {self.rule!r}")
        if self.engine not in ("flat", "stacked"):
            raise ValueError(f"bad engine {self.engine!r}")
        if self.backend not in ("pallas", "pallas_fused", "xla"):
            raise ValueError(f"bad backend {self.backend!r}")
        if (self.backend == "pallas_fused"
                and jnp.dtype(self.acc_dtype) != jnp.float32):
            raise ValueError("backend 'pallas_fused' needs float32 "
                             f"accumulators, got {jnp.dtype(self.acc_dtype)}")
        if self.T0 > self.T1:
            raise ValueError("need T0 <= T1")
        if self.rule == "theoretical" and self.thresh0 <= 0:
            raise ValueError("theoretical rule needs explicit thresholds")

    @staticmethod
    def theoretical_thresholds(T0: int, T1: int, m: int, p: float = 0.01,
                               V: float = 1.0):
        """Paper Lemma 3.2 / B.2 thresholds ``8 sqrt(T log(16 m T / p))``.

        ``V`` rescales for gradient-noise bound != 1 (the paper normalizes
        V = 1; thresholds are proportional to V).
        """
        import math
        t0 = 8.0 * V * math.sqrt(T0 * math.log(16 * m * T1 / p)) / m
        t1 = 8.0 * V * math.sqrt(T1 * math.log(16 * m * T1 / p)) / m
        return t0, t1


# --------------------------------------------------------------------------
# State
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SafeguardState:
    """Carried across steps.

    ``A``/``B`` are ``(m, d_pad)`` flat buffers under the flat engine,
    stacked pytrees under the stacked engine, and ``(m, r*k)`` sketch
    matrices in sketched mode.  ``layout`` is static pytree *metadata*
    (``None`` unless the flat engine is active)."""
    good: jax.Array             # (m,) bool — currently-good mask
    step: jax.Array             # () int32
    A: Any                      # long-window accumulator (None in single mode)
    B: Any                      # short-window accumulator
    evicted_at: jax.Array       # (m,) int32, -1 if never evicted (diagnostic)
    layout: Optional[FlatLayout] = None


jax.tree_util.register_dataclass(
    SafeguardState,
    data_fields=("good", "step", "A", "B", "evicted_at"),
    meta_fields=("layout",))


def init_state(cfg: SafeguardConfig, grads_like) -> SafeguardState:
    """``grads_like``: a parameter pytree (NOT stacked) used for shapes."""
    layout = None
    # A and B are always distinct buffers: a step that donates its state
    # may not receive one buffer twice
    if cfg.use_sketch or cfg.engine == "flat":
        if cfg.use_sketch:
            shape, dtype = (cfg.m, cfg.sketch_reps * cfg.sketch_k), jnp.float32
        else:
            layout = make_layout(grads_like)
            shape, dtype = (cfg.m, layout.d_padded), cfg.acc_dtype
        A = jnp.zeros(shape, dtype) if cfg.mode == "double" else None
        B = jnp.zeros(shape, dtype)
    else:
        def stacked(leaf):
            return jnp.zeros((cfg.m,) + leaf.shape, cfg.acc_dtype)
        acc = jax.tree.map(stacked, grads_like)
        A = acc if cfg.mode == "double" else None
        B = jax.tree.map(stacked, grads_like)
    return SafeguardState(
        good=jnp.ones((cfg.m,), bool),
        step=jnp.zeros((), jnp.int32),
        A=A,
        B=B,
        evicted_at=-jnp.ones((cfg.m,), jnp.int32),
        layout=layout,
    )


# --------------------------------------------------------------------------
# Filter internals
# --------------------------------------------------------------------------

def _empirical_filter(sqdist: jax.Array, good: jax.Array, m: int,
                      scale: float, floor: float):
    """Appendix C.1: score_i = ceil(m/2+1)-th smallest distance over good j;
    med = argmin score;  evict j with d(j, med) >= scale * max(S, floor).

    Returns (pass mask, med index, threshold, scores).
    """
    big = jnp.float32(1e30)
    # decision-site clamp: every sqdist producer clips at 0, but a negative
    # from f32 cancellation slipping through would turn sqrt into NaN and a
    # NaN distance compares False against the threshold — silently evicting
    # honest workers.  Never trust the upstream here.
    dist = jnp.sqrt(jnp.maximum(sqdist, 0.0))
    # mask non-good rows/cols
    dist = jnp.where(good[None, :], dist, big)
    dist = jnp.where(good[:, None], dist, big)
    k = int(-(-m // 2)) + 1        # ceil(m/2) + 1 entries -> index k-1
    k = min(k, m)
    sorted_d = jnp.sort(dist, axis=1)
    scores = sorted_d[:, k - 1]
    scores = jnp.where(good, scores, big)
    med = jnp.argmin(scores)
    S = scores[med]
    thresh = scale * jnp.maximum(S, floor)
    ok = dist[:, med] < thresh
    ok = ok | (jnp.arange(m) == med)
    return ok & good, med, thresh, scores


def _theoretical_filter(sqdist: jax.Array, good: jax.Array, m: int,
                        thresh: float):
    """Paper Algorithm 1 lines 9-11: med = any good i with a strict majority
    of workers within ``thresh``;  evict at ``2 * thresh``."""
    big = jnp.float32(1e30)
    dist = jnp.sqrt(jnp.maximum(sqdist, 0.0))   # see _empirical_filter
    dist = jnp.where(good[None, :], dist, big)
    dist = jnp.where(good[:, None], dist, big)
    within = (dist <= thresh) & good[None, :] & good[:, None]
    counts = within.sum(axis=1)
    valid = good & (counts > m // 2)
    # fall back to max-count worker when the majority event fails
    counts_masked = jnp.where(good, counts, -1)
    med = jnp.where(valid.any(), jnp.argmax(valid), jnp.argmax(counts_masked))
    ok = dist[:, med] <= 2.0 * thresh
    ok = ok | (jnp.arange(m) == med)
    return ok & good, med, jnp.float32(2.0 * thresh), counts.astype(jnp.float32)


def _accumulate_exact(acc, grads, reset, inv_ngood, dtype):
    """acc <- [reset ? 0 : acc] + grads / n_good, in acc dtype."""
    def one(a, g):
        a = jnp.where(reset, jnp.zeros_like(a), a)
        return a + g.astype(dtype) * inv_ngood
    return jax.tree.map(one, acc, grads)


def _accumulate_flat(acc, grads, reset, scale, layout: FlatLayout):
    """acc <- [reset ? 0 : acc] + flatten(grads) * scale, as ONE fused
    in-place chain: the reset ``where`` materializes the new buffer once
    and every per-leaf column-slice add after it updates that buffer in
    place — no intermediate ``(m, d)`` flattened-gradient matrix."""
    buf = jnp.where(reset, jnp.zeros_like(acc), acc)
    leaves = jax.tree_util.tree_leaves(grads)
    m = leaves[0].shape[0]
    for leaf, off in zip(leaves, layout.offsets):
        r = (leaf.astype(jnp.float32).reshape(m, -1)
             * scale).astype(acc.dtype)
        buf = buf.at[:, off:off + r.shape[1]].add(r)
    return buf


def _flat_sqdist(buf, cfg: SafeguardConfig):
    """Pairwise squared distances of the flat accumulator: blocked Pallas
    Gram kernel (one block under the CPU interpreter) or the oracle's XLA
    multiply-reduce (shardable by XLA; on a ``(data=4, model=1)`` v5e mesh
    it all-gathers the worker rows)."""
    if cfg.backend == "pallas":
        from repro.kernels.safeguard_filter import pairwise_sqdist
        return pairwise_sqdist(buf, block_d=None, interpret=not _on_tpu())
    from repro.kernels.safeguard_filter import ref as sf_ref
    return sf_ref.pairwise_sqdist(buf)


def _flat_update(acc, grads, gflat, reset, scale, cfg: SafeguardConfig,
                 layout: FlatLayout):
    """One accumulator's flat-engine update -> (new_acc, sqdist).

    ``gflat`` is the flattened gradient matrix, materialized by the caller
    only for the ``pallas_fused`` backend (``None`` otherwise)."""
    if gflat is not None:
        from repro.kernels.safeguard_filter import fused_accumulate_sqdist
        return fused_accumulate_sqdist(acc, gflat, reset, scale,
                                       interpret=not _on_tpu())
    new = _accumulate_flat(acc, grads, reset, scale, layout)
    return new, _flat_sqdist(new, cfg)


# --------------------------------------------------------------------------
# The step
# --------------------------------------------------------------------------

def safeguard_step(state: SafeguardState, grads, cfg: SafeguardConfig,
                   rng: Optional[jax.Array] = None, *,
                   acc_sharding=None):
    """One master-side safeguard step.

    Args:
      state:  SafeguardState.
      grads:  stacked per-worker gradient pytree, leaves ``(m, ...)``.  The
        Byzantine simulation (attacks) has already been applied.
      cfg:    SafeguardConfig.
      rng:    PRNG key for the Gaussian perturbation (required if nu > 0).
      acc_sharding: optional ``NamedSharding`` pinned onto the flat gradient
        buffer (and hence the accumulators) so the worker rows stay on the
        ``data`` mesh axes under a sharded jit (DESIGN.md §3).

    Returns:
      (new_state, aggregated gradient pytree, info dict)
    """
    m = cfg.m
    t = state.step
    good = state.good

    # Section 5 relaxation: periodically restore every worker.  A restored
    # worker's ``evicted_at`` diagnostic is cleared too — otherwise the
    # post-reset eviction times (fig2b trace) would keep reporting the
    # pre-reset eviction forever.
    restored = jnp.zeros_like(good)
    evicted_at = state.evicted_at
    if cfg.reset_period > 0:
        restore = (t % cfg.reset_period) == 0
        restored = restore & ~good
        good = jnp.where(restore, jnp.ones_like(good), good)
        evicted_at = jnp.where(restored, -1, evicted_at)

    n_good = jnp.maximum(good.sum(), 1).astype(jnp.float32)
    inv_ngood = 1.0 / n_good

    reset_B = (t % cfg.T0) == 0
    reset_A = (t % cfg.T1) == 0

    if cfg.use_sketch:
        gsk = sk.sketch_tree(grads, k=cfg.sketch_k, reps=cfg.sketch_reps,
                             seed=cfg.sketch_seed)
        B = jnp.where(reset_B, 0.0, state.B) + gsk * inv_ngood
        A = None
        if cfg.mode == "double":
            A = jnp.where(reset_A, 0.0, state.A) + gsk * inv_ngood
        sqdist_B = sk.sketch_pairwise_sqdist(B)
        sqdist_A = sk.sketch_pairwise_sqdist(A) if A is not None else None
    elif cfg.engine == "flat":
        layout = state.layout
        gflat = (flatten_stacked(grads, layout)
                 if cfg.backend == "pallas_fused" else None)
        B, sqdist_B = _flat_update(state.B, grads, gflat, reset_B,
                                   inv_ngood, cfg, layout)
        A, sqdist_A = None, None
        if cfg.mode == "double":
            A, sqdist_A = _flat_update(state.A, grads, gflat, reset_A,
                                       inv_ngood, cfg, layout)
        if acc_sharding is not None:
            B = jax.lax.with_sharding_constraint(B, acc_sharding)
            if A is not None:
                A = jax.lax.with_sharding_constraint(A, acc_sharding)
    else:
        B = _accumulate_exact(state.B, grads, reset_B, inv_ngood,
                              cfg.acc_dtype)
        A = None
        if cfg.mode == "double":
            A = _accumulate_exact(state.A, grads, reset_A, inv_ngood,
                                  cfg.acc_dtype)
        sqdist_B = tu.tree_pairwise_sqdist(B)
        sqdist_A = tu.tree_pairwise_sqdist(A) if A is not None else None

    if cfg.rule == "empirical":
        okB, medB, thB, scoresB = _empirical_filter(
            sqdist_B, good, m, cfg.threshold_scale, cfg.threshold_floor)
        if cfg.mode == "double":
            okA, medA, thA, _ = _empirical_filter(
                sqdist_A, good, m, cfg.threshold_scale, cfg.threshold_floor)
        else:
            okA, medA, thA = jnp.ones_like(okB), medB, thB
    else:
        okB, medB, thB, scoresB = _theoretical_filter(
            sqdist_B, good, m, cfg.thresh0)
        if cfg.mode == "double":
            okA, medA, thA, _ = _theoretical_filter(
                sqdist_A, good, m, cfg.thresh1)
        else:
            okA, medA, thA = jnp.ones_like(okB), medB, thB

    new_good = good & okA & okB

    newly_evicted = good & ~new_good
    evicted_at = jnp.where(newly_evicted, t, evicted_at)

    # SGD direction over good_t (pre-filter, paper line 12) or good_{t+1}.
    agg_mask = good if cfg.aggregate_prefilter else new_good
    agg = tu.tree_masked_mean(grads, agg_mask)

    if cfg.nu > 0.0:
        if rng is None:
            raise ValueError("nu > 0 requires an rng key")
        keys = jax.random.split(rng, len(jax.tree_util.tree_leaves(agg)))
        keys = iter(list(keys))

        def add_noise(leaf):
            k = next(keys)
            return leaf + cfg.nu * jax.random.normal(k, leaf.shape, leaf.dtype)
        agg = jax.tree.map(add_noise, agg)

    new_state = SafeguardState(
        good=new_good,
        step=t + 1,
        A=A if cfg.mode == "double" else state.A,
        B=B,
        evicted_at=evicted_at,
        layout=state.layout,
    )
    dist_B = jnp.sqrt(jnp.maximum(sqdist_B, 0.0))[:, medB]
    dist_A = (jnp.sqrt(jnp.maximum(sqdist_A, 0.0))[:, medA]
              if sqdist_A is not None else dist_B)
    info = {
        "n_good": n_good,
        "med_B": medB,
        "med_A": medA,
        "threshold_B": thB,
        "threshold_A": thA,
        "dist_to_med_B": dist_B,
        "dist_to_med_A": dist_A,
        "scores_B": scoresB,
        "newly_evicted": newly_evicted,
        "restored": restored,
        "good": new_good,
    }
    return new_state, agg, info
