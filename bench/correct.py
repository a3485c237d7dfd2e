"""The comparison that decides ``correct``: one experiment of the window,
drawn from the seed, against the plain reference run over the same
configuration, seed and noise multiplier.

Numbers compared, each against the cell's limit in
``bench/limits/<cell>.json``:

* ``tree_dist`` — the distance between the program's and the
  reference's final global params over all leaves together,
  ``||prog - ref||``, over the reference's change ``||ref - init||``.
  Steady from seed to seed: the large leaves carry it;
* ``param_dist`` — the worst leaf's distance between the program's and
  the reference's final global params, ``||prog - ref||``, over the
  reference's change of that leaf from the initial params,
  ``||ref - init||``, or the median leaf's change, whichever is larger.
  Both sides draw the same DP noise from the same keys, so only the
  arithmetic differs;
* ``change_gap`` — the worst leaf's gap between the two norms of the
  change, ``| ||prog - init|| - ||ref - init|| |``, over the same
  denominator;
* ``eps_gap`` — the largest relative gap between the program's and the
  reference's epsilon over every merged update;
* ``books_diff`` — how many of the books differ: merged updates and
  staleness per tier, the virtual times of the evals, the cohort sizes
  (an exact comparison: limit 0).
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("tree_dist", "param_dist", "change_gap", "eps_gap", "books_diff")


def _leaves(tree) -> list:
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def leaf_readings(init, prog, ref) -> list:
    """Per leaf: ``(path, ||prog - ref||, ||ref - init||, ||prog - init||,
    size)``."""
    import jax
    paths = [jax.tree_util.keystr(k)
             for k, _ in jax.tree_util.tree_leaves_with_path(ref)]
    return [(k, float(np.linalg.norm(p - r)), float(np.linalg.norm(r - a)),
             float(np.linalg.norm(p - a)), int(r.size))
            for k, a, p, r in zip(paths, _leaves(init), _leaves(prog),
                                  _leaves(ref))]


def param_numbers(init, prog, ref) -> dict:
    leaves = leaf_readings(init, prog, ref)
    if not all(np.isfinite(np.asarray(x, np.float64)).all()
               for x in _leaves(prog)):
        return {"tree_dist": math.inf, "param_dist": math.inf,
                "change_gap": math.inf}
    floor = float(np.median([c for _, _, c, _, _ in leaves]))
    dist = max(d / max(c, floor) for _, d, c, _, _ in leaves)
    gap = max(abs(m - c) / max(c, floor) for _, _, c, m, _ in leaves)
    tree = (math.sqrt(sum(d * d for _, d, _, _, _ in leaves))
            / math.sqrt(sum(c * c for _, _, c, _, _ in leaves)))
    return {"tree_dist": tree, "param_dist": dist, "change_gap": gap}


def books_numbers(books_prog: dict, books_ref: dict) -> dict:
    """``books_*``: update_counts / staleness / eps per tier, times and
    cohort_sizes, as :func:`program_books` and the reference give them."""
    diff = 0
    tiers = sorted(set(books_prog["update_counts"]) | set(books_ref["update_counts"]))
    eps_gap = 0.0
    for t in tiers:
        diff += books_prog["update_counts"].get(t) != books_ref["update_counts"].get(t)
        diff += books_prog["staleness"].get(t, []) != books_ref["staleness"].get(t, [])
        ep, er = books_prog["eps"].get(t, []), books_ref["eps"].get(t, [])
        if len(ep) != len(er):
            eps_gap = math.inf
            continue
        for a, b in zip(ep, er):
            eps_gap = max(eps_gap, abs(a - b) / abs(b) if b else abs(a))
    diff += books_prog["times"] != books_ref["times"]
    diff += books_prog["cohort_sizes"] != books_ref["cohort_sizes"]
    return {"eps_gap": eps_gap, "books_diff": int(diff)}


def program_books(log) -> dict:
    return {"update_counts": dict(log.update_counts),
            "staleness": {t: list(v) for t, v in log.staleness.items() if v},
            "eps": {t: list(v) for t, v in log.eps_trajectory.items() if v},
            "times": list(log.times),
            "cohort_sizes": list(log.cohort_sizes)}


def compare(init, prog, books_prog, ref, books_ref) -> dict:
    out = param_numbers(init, prog, ref)
    out.update(books_numbers(books_prog, books_ref))
    return out


def limits_for(spec: dict, sigma: float) -> dict:
    """The limits of one experiment: a cell's limits file holds the
    limits every experiment shares (``limits``) and, under ``by_sigma``,
    those of each noise multiplier of its mix, keyed by the multiplier
    as ``repr(float(sigma))``: the DP noise sets the scale against which
    a fault in the signal shows, so the parameter numbers are read per
    multiplier."""
    out = dict(spec["limits"])
    by = spec.get("by_sigma", {})
    if by:
        key = repr(float(sigma))
        if key not in by:
            raise KeyError(f"no limits for sigma {key}: {sorted(by)}")
        out.update(by[key])
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """``(ok, checks)``: every number at or under its limit, and the
    numbers with their limits in the order of :data:`NUMBERS`."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS
              if k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
