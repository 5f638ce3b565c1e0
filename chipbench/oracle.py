"""The correctness check of a training cell.

The run's first ``STEPS`` steps go through the window's own step and feed
during set-up; :func:`program_readings` reads them.  Once the window has
closed and the run's state is freed, :func:`follow` takes the same steps
with the plain reference of the architecture and of the protocol (the
attack, the safeguard's accumulators and filter, the mean, SGD), and
:func:`compare` sets the two readings side by side.  :func:`tables` gives
every number's table; a cell compares the numbers its limits name, each
the worst entry of its table:

* ``loss``: each step's mean loss, relative gap;
* ``grad``: the first gradient as the optimizer gets it, per leaf: where
  the defense keeps flat accumulators, each worker's row of the
  short-window accumulator after one step (``n_good`` times ``g_i /
  n_good``, after the attack); otherwise the parameters' change after one
  step;
* ``update``: the parameters' change after ``STEPS`` steps, per leaf;
* ``moved_diff``: the norm of the difference of the first step's change
  from the reference's, on the leaves that step moves in nearly every
  element (there the change is the gradient itself, not the few elements
  whose update clears a rounding step of the stored bf16);
* ``dist``: every worker's distance to the filter's median, against the
  reference's distance to the same worker;
* ``good``: the good set after every step, counted in mismatches.

``grad`` and ``update`` take the gap between the program's norm and the
reference's, ``moved_diff`` the norm of the difference; each over the
larger of the reference's norm of that leaf and of the median leaf.
Leaves whose reference gradient is under ``TINY_LEAF`` of the median
leaf's are left out.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench

STEPS = 3
TINY_LEAF = 1e-3
MOVED = 0.99
f32 = jnp.float32


def leaf_dict(tree) -> dict:
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def host_copy(tree) -> dict:
    return {k: np.asarray(v)
            for k, v in leaf_dict(jax.device_get(tree)).items()}


@jax.jit
def _delta_norms(a0, a1, b0, b1):
    """Per leaf: ``||a1 - a0||``, ``||b1 - b0||``, the norm of the
    difference of the two changes, and the share of ``b``'s elements that
    changed."""
    out = {}
    for k in a0:
        da = a1[k].astype(f32) - a0[k].astype(f32)
        db = b1[k].astype(f32) - b0[k].astype(f32)
        out[k] = jnp.stack([jnp.linalg.norm(da), jnp.linalg.norm(db),
                            jnp.linalg.norm(da - db),
                            (db != 0).mean(dtype=f32)])
    return out


def delta_norms(a0, a1, b0, b1) -> dict:
    dev = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    out = _delta_norms(dev(a0), dev(a1), dev(b0), dev(b1))
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


# --------------------------------------------------------------------------
# The run's readings
# --------------------------------------------------------------------------

def _row_norms(layout):
    """Per-leaf norms of each worker's row of a flat buffer."""
    spans = list(zip(layout.offsets, layout.sizes))

    @jax.jit
    def norms(buf):
        return jnp.stack([jnp.sqrt((buf[:, o:o + s] ** 2).sum(axis=1))
                          for o, s in spans])
    return norms


def program_readings(trainer, step_metrics: list) -> dict:
    """Take the run's first ``STEPS`` steps through ``trainer`` (its own
    step and feed) and read them.  ``step_metrics`` is the list the step
    wrapper appends each step's metrics to.  The parameters are copied to
    the host, so that nothing of the check stays on the device."""
    state = trainer.state
    paths = list(leaf_dict(state.params))
    ds = state.defense_state
    flat = getattr(ds, "layout", None) is not None
    n_good0 = float(np.asarray(ds.good).sum()) if flat else None
    out = {"p0": host_copy(state.params)}
    for t in range(STEPS):
        trainer.run(1, verbose=False)
        if t == 0:
            out["p1"] = host_copy(trainer.state.params)
            if flat:
                ds = trainer.state.defense_state
                rows = np.asarray(_row_norms(ds.layout)(ds.B)) * n_good0
                out["grad"] = dict(zip(paths, rows))
    out["p3"] = host_copy(trainer.state.params)
    mets = step_metrics[-STEPS:]
    out["loss"] = [float(mt["loss"]) for mt in mets]
    if "dist_to_med_B" in mets[0]:
        out["dist"] = np.stack([np.asarray(mt["dist_to_med_B"], np.float64)
                                for mt in mets])
        out["good"] = np.stack([np.asarray(mt["good"]) for mt in mets])
    return out


# --------------------------------------------------------------------------
# The reference
# --------------------------------------------------------------------------

def _empirical_filter(sqdist, good, scale, floor):
    """Appendix C.1 of the paper: a worker's score is its
    ``ceil(m/2)+1``-th smallest distance to the good workers; the median is
    the good worker of least score; a worker at or beyond
    ``scale * max(score, floor)`` of the median is evicted."""
    m = len(good)
    dist = np.sqrt(np.maximum(sqdist, 0.0))
    dist[~good, :] = 1e30
    dist[:, ~good] = 1e30
    k = min(-(-m // 2) + 1, m)
    scores = np.sort(dist, axis=1)[:, k - 1]
    scores[~good] = 1e30
    med = int(np.argmin(scores))
    ok = dist[:, med] < scale * max(scores[med], floor)
    ok[med] = True
    return ok & good, med


@jax.jit
def _acc_update(acc, g, keep, inv_n):
    return jax.tree.map(lambda a, x: a * keep + x * inv_n, acc, g)


@jax.jit
def _sqdist(a, b):
    return sum(((x - y) ** 2).sum() for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _pairwise(rows) -> np.ndarray:
    m = len(rows)
    sq = np.zeros((m, m))
    for i in range(m):
        for j in range(i + 1, m):
            sq[i, j] = sq[j, i] = float(_sqdist(rows[i], rows[j]))
    return sq


@jax.jit
def _add(acc, g, w):
    return jax.tree.map(lambda a, x: a + w * x, acc, g)


@jax.jit
def _sgd(p, agg, lr):
    return jax.tree.map(lambda w, d: (w.astype(f32) - lr * d).astype(w.dtype),
                        p, agg)


@jax.jit
def _norms(tree):
    return {k: jnp.linalg.norm(v) for k, v in leaf_dict(tree).items()}


def _host(d):
    return {k: float(v) for k, v in d.items()}


@functools.lru_cache(maxsize=None)
def _reference_fns(model_json: str, precision: str, half: bool):
    """The jitted ``init(key)`` and per-worker ``value_and_grad`` of one
    architecture's reference, built once per process."""
    model = json.loads(model_json)
    ref = bench.reference(model["model_type"])
    init = jax.jit(lambda k: ref.init(model, k))
    vg = jax.jit(jax.value_and_grad(
        lambda p, toks: ref.loss(p, toks, model, precision=precision,
                                 half=half)))
    return init, vg


def follow(cell: dict, seed: int, batches: list, *, precision: str = "f32",
           fault: str | None = None) -> dict:
    """The reference's readings of the cell's first ``STEPS`` steps on
    ``batches``.  ``precision="fp8"`` is the control; ``fault="half_batch"``
    plants the fault of a loss taken over half of each worker's batch."""
    model, tr = cell["config"]["model"], cell["traffic"]
    m, n_byz = tr["workers"], tr["byzantine"]
    if tr["attack"] not in ("none", "sign_flip"):
        raise ValueError(f"the reference knows no attack {tr['attack']!r}")
    sg = tr["defense"] == "safeguard_double"
    if not sg and tr["defense"] != "mean":
        raise ValueError(f"the reference knows no defense {tr['defense']!r}")
    init, vg = _reference_fns(json.dumps(model), precision,
                              fault == "half_batch")
    p = init(jax.random.PRNGKey(seed))
    p0 = p
    good = np.ones(m, bool)
    A = B = None
    out = {"loss": [], "dist": [], "dist_matrix": [], "good": [], "grad": {},
           "p0": leaf_dict(p0)}
    for t in range(STEPS):
        pf = jax.tree.map(lambda a: a.astype(f32), p)
        toks = batches[t]["tokens"]
        n_good = max(int(good.sum()), 1)
        agg = jax.tree.map(jnp.zeros_like, pf)
        losses = []
        for i in range(m):
            loss, g = vg(pf, toks[i])
            losses.append(float(loss))
            if tr["attack"] == "sign_flip" and i < n_byz:
                g = jax.tree.map(jnp.negative, g)
            if t == 0:
                for k, v in _host(_norms(g)).items():
                    out["grad"].setdefault(k, []).append(v)
            if sg:
                if A is None:
                    A = [jax.tree.map(jnp.zeros_like, pf) for _ in range(m)]
                    B = [jax.tree.map(jnp.zeros_like, pf) for _ in range(m)]
                keep_a = float(t % tr["t1"] != 0)
                keep_b = float(t % tr["t0"] != 0)
                A[i] = _acc_update(A[i], g, keep_a, 1.0 / n_good)
                B[i] = _acc_update(B[i], g, keep_b, 1.0 / n_good)
            if not sg or good[i]:
                agg = _add(agg, g, 1.0 / (n_good if sg else m))
            del g
        out["loss"].append(float(np.mean(losses)))
        if t == 0:
            out["agg"] = _host(_norms(agg))
        if sg:
            sq = {"A": _pairwise(A), "B": _pairwise(B)}
            ok_a, _ = _empirical_filter(sq["A"], good, tr["threshold_scale"],
                                        tr["floor"])
            ok_b, med = _empirical_filter(sq["B"], good,
                                          tr["threshold_scale"], tr["floor"])
            good = good & ok_a & ok_b
            dist = np.sqrt(np.maximum(sq["B"], 0.0))
            out["dist_matrix"].append(dist)
            out["dist"].append(dist[:, med])
            out["good"].append(good.copy())
        p = _sgd(p, agg, tr["lr"])
        if t == 0:
            out["p1"] = leaf_dict(p)
        del pf, agg
    out["p3"] = leaf_dict(p)
    out["grad"] = {k: np.asarray(v) for k, v in out["grad"].items()}
    if sg:
        for k in ("dist", "dist_matrix", "good"):
            out[k] = np.stack(out[k])
    else:
        del out["dist"], out["dist_matrix"], out["good"]
    return out


# --------------------------------------------------------------------------
# The comparison
# --------------------------------------------------------------------------

def _relative(num: dict, ref: dict, counted) -> dict:
    """Per counted leaf (and worker): ``num`` over the larger of the
    reference's norm of that leaf and of the median leaf."""
    keys = [k for k in ref if k in counted]
    ref_arr = np.stack([np.atleast_1d(ref[k]) for k in keys])
    num_arr = np.stack([np.atleast_1d(num[k]) for k in keys])
    den = np.maximum(ref_arr, np.median(ref_arr, axis=0, keepdims=True))
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.where(num_arr == 0, 0.0, num_arr / den)
    return dict(zip(keys, rel))


def _worst(rel: dict) -> float:
    return float(np.max(np.stack(list(rel.values()))))


def tables(prog: dict, ref: dict) -> dict:
    """Every number's table before it is reduced to one value: per leaf
    (and worker) for the gradient and the update, per step and worker for
    the distances."""
    agg = ref["agg"]
    tiny = TINY_LEAF * float(np.median(list(agg.values())))
    counted = {k for k, v in agg.items() if v >= tiny}
    if set(prog["p0"]) != set(ref["p0"]):
        raise ValueError("the run's and the reference's parameters differ "
                         f"in their leaves: {sorted(prog['p0'])} vs "
                         f"{sorted(ref['p0'])}")
    first = delta_norms(prog["p0"], prog["p1"], ref["p0"], ref["p1"])
    third = delta_norms(prog["p0"], prog["p3"], ref["p0"], ref["p3"])
    col = lambda d, i: {k: v[i] for k, v in d.items()}
    gap = lambda a, b: {k: abs(a[k] - b[k]) for k in b}
    moved = {k for k, v in first.items() if v[3] >= MOVED} & counted
    if not moved:
        raise ValueError("the first step moves no leaf in nearly every "
                         "element: moved_diff has nothing to compare")
    out = {"loss": np.array([abs(a - b) / abs(b)
                             for a, b in zip(prog["loss"], ref["loss"])]),
           "update": _relative(gap(col(third, 0), col(third, 1)),
                               col(third, 1), counted),
           "moved_diff": _relative(col(first, 2), col(first, 1), moved)}
    if "dist" in ref:
        out["grad"] = _relative(gap(prog["grad"], ref["grad"]), ref["grad"],
                                counted)
        # each step's distances to the run's median, read from the
        # reference's full distance matrix: a near-tie of the medians'
        # scores may pick another median without changing a distance
        med = np.argmin(prog["dist"], axis=1)
        full = ref["dist_matrix"]
        ref_d = np.stack([full[t][:, k] for t, k in enumerate(med)])
        out["dist"] = (np.abs(prog["dist"] - ref_d)
                       / full.max(axis=(1, 2))[:, None])
        out["good"] = (prog["good"] != ref["good"]).astype(int)
    else:
        out["grad"] = _relative(gap(col(first, 0), col(first, 1)),
                                col(first, 1), counted)
    return out


def compare(prog: dict, ref: dict, limits: dict | None) -> dict:
    """``{name: {"value", "limit"}}`` for every number that ``limits``
    names, or for every number with no limit when ``limits`` is None: the
    worst entry of its table (mismatches counted for ``good``)."""
    values, found = {}, tables(prog, ref)
    missing = set(limits or ()) - set(found)
    if missing:
        raise ValueError(f"limits name numbers this cell does not have: "
                         f"{sorted(missing)}")
    for name, table in found.items():
        if limits is not None and name not in limits:
            continue
        if name == "good":
            values[name] = int(table.sum())
        elif isinstance(table, dict):
            values[name] = _worst(table)
        else:
            values[name] = float(np.max(table))
    return {k: {"value": v, "limit": (limits or {}).get(k, math.inf)}
            for k, v in values.items()}


def judge(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
