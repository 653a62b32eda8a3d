"""The comparison that decides `correct`: numbers, each beside a limit of its
own (a configuration's `checks`), computed from what the timed path
produced and what the plain reference says. PERF.md section 2 has the
readings each limit was set from."""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Sequence


def leaf_norms(tree) -> Dict[str, float]:
    """{op/weight: L2 norm in float32}, fetched in one transfer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    flat = {f"{op}/{w}": a for op, ws in tree.items() for w, a in ws.items()}
    keys = sorted(flat)
    vals = np.asarray(jax.jit(lambda xs: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]))(
            [flat[k] for k in keys]))
    return {k: float(x) for k, x in zip(keys, vals)}


def delta_norms(after, before) -> Dict[str, float]:
    """Per-leaf L2 norm of (after - before)."""
    import jax
    import jax.numpy as jnp

    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))(
            after, before)
    return leaf_norms(diff)


def norm_gaps(got: Dict[str, float], want: Dict[str, float],
              leaves: Iterable[str]) -> Dict[str, float]:
    """Per leaf the gap between the program's norm and the reference's (a
    gap of norms, not the norm of a difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    leaves = list(leaves)
    floor = statistics.median(want[k] for k in leaves)
    return {k: abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
            for k in leaves}


def worst(gaps: Dict[str, float]):
    """(largest gap, its leaf); a NaN anywhere is the answer: nothing was
    compared there, and that is never "ok"."""
    for k, g in gaps.items():
        if g != g:
            return float("nan"), k
    k = max(gaps, key=gaps.get)
    return float(gaps[k]), k


def median(gaps: Dict[str, float]) -> float:
    vals = list(gaps.values())
    if any(v != v for v in vals):
        return float("nan")
    return float(statistics.median(vals))


def rel_diffs(got_tree, want_tree) -> Dict[str, float]:
    """Per leaf ||got - want|| / max(||want||, median leaf's ||want||): how
    far the program's tensor is from the reference's, not only its size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    keys = sorted((op, w) for op, ws in want_tree.items() for w in ws)

    @jax.jit
    def both(gs, ws):
        d = [jnp.sqrt(jnp.sum(jnp.square(
            g.astype(jnp.float32) - w.astype(jnp.float32))))
            for g, w in zip(gs, ws)]
        n = [jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32))))
             for w in ws]
        return jnp.stack(d), jnp.stack(n)

    d, n = both([jnp.asarray(got_tree[op][w]) for op, w in keys],
                [want_tree[op][w] for op, w in keys])
    d, n = np.asarray(d, np.float64), np.asarray(n, np.float64)
    floor = float(np.median(n))
    return {f"{op}/{w}": float(di / max(ni, floor, 1e-30))
            for (op, w), di, ni in zip(keys, d, n)}


def moving_leaves(grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's. The others (a key's bias under softmax)
    move under Adam by round-off alone and are left out of the update's
    comparison — by this rule, not by name."""
    floor = 1e-3 * statistics.median(grad_norms.values())
    return sorted(k for k, v in grad_norms.items() if v >= floor)


def train_checks(prog: Dict, ref: Dict, limits: Dict):
    """prog / ref: {"loss": mean loss of the compared steps, "moment_norms",
    "delta_norms"}; prog["moment_rel_diffs"] from `rel_diffs` against the
    reference's moments; ref["grad1_norms"]. Returns the compared numbers
    (one entry per limit; `loss_gap` is the gap of the K steps' mean loss,
    all that `fit` hands back) and notes with the readings that are NOT
    compared (PERF.md section 2 says why): the moments' norm gaps."""
    all_leaves = sorted(ref["moment_norms"])
    movers = moving_leaves(ref["grad1_norms"])
    mom = norm_gaps(prog["moment_norms"], ref["moment_norms"], all_leaves)
    upd = norm_gaps(prog["delta_norms"], ref["delta_norms"], movers)
    upd_worst, upd_leaf = worst(upd)
    mom_worst, mom_leaf = worst(mom)
    rel_worst, rel_leaf = worst(prog["moment_rel_diffs"])
    values = {"loss_gap": abs(prog["loss"] - ref["loss"])
              / max(abs(ref["loss"]), 1e-30),
              "moment_norm_gap_median": median(mom),
              "moment_rel_diff_median": median(prog["moment_rel_diffs"]),
              "moment_rel_diff_worst": rel_worst,
              "update_norm_gap": upd_worst}
    out = {name: {"value": float(values[name]), "limit": float(limit)}
           for name, limit in limits.items()}
    notes = {"moment_norm_gap_worst": mom_worst, "moment_leaf": mom_leaf,
             "moment_rel_diff_leaf": rel_leaf,
             "update_norm_gap_median": median(upd), "update_leaf": upd_leaf,
             "leaves_compared": len(movers), "leaves": len(all_leaves),
             **{f"all_{k}": v for k, v in values.items()}}
    return out, notes


def serve_checks(gaps: Sequence, limits: Dict, unanswered: int = 0):
    """gaps: per sampled request, the per-token logit gaps of the served
    tokens below the reference's best. Two numbers: the widest gap, which
    swings by its nature, and the share of served tokens that are not the
    reference's best at all, which is steady from seed to seed."""
    import numpy as np

    flat = np.concatenate([np.asarray(g, np.float64) for g in gaps]) \
        if len(gaps) else np.array([np.inf])
    values = {"served_logit_gap_max": float(flat.max()),
              "served_off_best_share": float((flat > 0).mean()),
              "unanswered": float(unanswered)}
    out = {name: {"value": values[name], "limit": float(limit)}
           for name, limit in limits.items()}
    notes = {"tokens_compared": int(flat.size),
             "tokens_off_best": int((flat > 0).sum()),
             "gap_p99": float(np.percentile(flat, 99)),
             "gap_mean": float(flat.mean())}
    return out, notes
