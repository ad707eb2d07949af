"""What decides ``correct`` for a training cell: the numbers compared, each
against a limit of its own. "Outputs" are what the timed path handed over
(``lib/reference.train`` hands over the same, which is how the control and the
planted faults are read with the program out of the way):

    trees        every tree boosted, warm-up call first, as arrays
                 left/right/feat/thr/value (+ sum_hess) -- the model as stated
    warm_rounds  how many of them the warm-up call boosted: the window's
                 rounds are ``trees[warm_rounds:]``
    base_margin  the starting margin
    margin       the booster's own training margin when the window closed
    eval_losses  the program's eval metric after each round (train-eval only)
    rounds_claimed  calls that returned (warm-up included) x rounds a call

The reference follows the WINDOW's first rounds: its walker carries the
warm-up's trees over the raw rows to the state the window started from, the
reference booster continues from that state for ``follow_rounds`` rounds, and
the window's first trees are held against it. A boosted model has one
parameter leaf in function space, the margin over the training rows, so "by
the worst leaf" is that one vector.

    loss_gap    max over the window's first rounds of |loss - ref loss| / ref
                loss; the program's loss after a round is read by walking ITS
                trees over the raw rows with the reference's walker
    grad_gap    |root sum-hessian - the reference's| / the reference's, the
                worse of two trees: the window's first (the gradient a
                continuation call took from the state it was handed; half the
                batch reads 0.5, a stale margin reads the hessian's drift) and
                the very first (the gradient from the seed, against the
                reference's own starting margin)
    update_gap  | ||m_R - m_w|| - ||ref m_R - m_w|| | / the reference's norm:
                the change of the parameters over the followed rounds, from the
                window's starting margin m_w
    margin_gap  max |booster's margin - walk of all its trees|, over the
                largest |margin|: ties the state the window really carried to
                the model it states, at the timed size, after the last call
    eval_gap    max over all rounds of |program's eval loss - loss of walking
                its trees over the held-out rows| / the latter
    rounds_gap  |trees stated - rounds claimed| / rounds claimed; exact
"""

from __future__ import annotations

import numpy as np

from . import reference as ref


def _rel(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def _norm(v: np.ndarray) -> float:
    return float(np.linalg.norm(v.astype(np.float64)))


def numbers(outputs: dict, X, y, params: dict, follow_rounds: int,
            X_eval=None, y_eval=None, reference_run: dict | None = None):
    """-> {name: value}. ``reference_run`` is passed in where several outputs
    that share their warm-up are read against one reference (the control)."""
    trees = outputs["trees"]
    warm = min(int(outputs["warm_rounds"]), len(trees))
    followed = trees[warm:warm + follow_rounds]
    base = np.float32(outputs["base_margin"])
    m = np.full(X.shape[0], base, np.float32)
    for tree in trees[:warm]:
        m = m + ref.walk(tree, X)
    m_start = m
    losses = []
    for tree in followed:
        m = m + ref.walk(tree, X)
        losses.append(ref.logloss(m, y))
    # rounds that are missing compare as the last state there is
    losses += [ref.logloss(m, y)] * (follow_rounds - len(followed))
    change = _norm(m - m_start)
    for tree in trees[warm + follow_rounds:]:
        m = m + ref.walk(tree, X)

    r = reference_run or ref.train(X, y, params, follow_rounds,
                                   start_margin=m_start)
    out = {"loss_gap": float(max(_rel(got, want)
                                 for got, want in zip(losses, r["losses"])))}
    out["update_gap"] = _rel(change, _norm(r["margin"] - m_start))

    def root_hess(some_trees):
        return float(some_trees[0]["sum_hess"][0]) if some_trees else 0.0
    _, h = ref.gradients(np.full(len(y), np.float32(ref.stump_margin(y))), y,
                         np.asarray)
    out["grad_gap"] = max(
        _rel(root_hess(followed), root_hess(r["trees"])),
        _rel(root_hess(trees), float(h.sum(dtype=np.float64))))

    state = np.asarray(outputs["margin"], np.float32).reshape(-1)
    if state.shape != m.shape or not np.isfinite(state).all():
        out["margin_gap"] = float("inf")
    else:
        out["margin_gap"] = float(np.abs(state - m).max()
                                  / max(float(np.abs(m).max()), 1e-30))
    if X_eval is not None:
        me = np.full(X_eval.shape[0], base, np.float32)
        got = list(outputs.get("eval_losses") or [])
        eg = [float("inf")] if len(got) != len(trees) else []
        for tree, g in zip(trees, got):
            me = me + ref.walk(tree, X_eval)
            eg.append(_rel(g, ref.logloss(me, y_eval)))
        out["eval_gap"] = float(max(eg)) if eg else float("inf")
    claimed = outputs["rounds_claimed"]
    out["rounds_gap"] = abs(len(trees) - claimed) / max(claimed, 1)
    return out


def judge(values: dict, limits: dict):
    """-> (correct, {name: [value, limit]}). Every limit needs its number and
    every number its limit; a number that is not finite is over its limit."""
    if set(values) != set(limits):
        raise ValueError(f"numbers compared {sorted(values)} and limits "
                         f"{sorted(limits)} differ")
    table = {k: [values[k], limits[k]] for k in sorted(values)}
    ok = all(np.isfinite(v) and v <= lim for v, lim in table.values())
    return bool(ok), table
