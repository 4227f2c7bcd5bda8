"""The OPT-alpha relay weights held to their problem, apart from the
system's ``core/opt_alpha.py``.

The problem (arXiv:2205.10998, eqs. 4-5): relay weights ``A[j, i]`` (relay
j, origin i), non-negative and zero unless j is i or a D2D neighbour of i,
such that every origin's update reaches the PS unbiased,
``sum_j p_j A[j, i] = 1``, with the least variance
``S = sum_j p_j (1 - p_j) (sum_i A[j, i])**2``.  Clients outside the
cohort, and relays whose uplink never fires (``p_j = 0``), carry nothing.

In flows ``f[j, i] = p_j A[j, i]`` every origin splits one unit over its
relays and ``S = sum_j c_j F_j**2`` with ``F_j = sum_i f[j, i]`` and
``c_j = (1 - p_j) / p_j``: convex over a product of simplices.  So the
Frank-Wolfe gap ``sum_i (sum_j g_j f[j, i] - min_j g_j)``, with
``g_j = 2 c_j F_j`` and the minimum over origin i's relays, bounds
``S(A) - min S`` from above, with no second solve: it is 0 exactly at an
optimum and grows with the distance from one.
"""
from __future__ import annotations

import numpy as np


def support(p, adj, active=None) -> np.ndarray:
    """``sup[j, i]``: relay j may carry origin i's update."""
    p = np.asarray(p, np.float64)
    n = len(p)
    a = np.ones(n, bool) if active is None else np.asarray(active, bool)
    closed = np.asarray(adj, bool) | np.eye(n, dtype=bool)
    return closed & a[:, None] & a[None, :] & (p > 0)[:, None]


def variance(p, A) -> float:
    p = np.asarray(p, np.float64)
    return float(np.sum(p * (1.0 - p) * np.asarray(A, np.float64).sum(axis=1) ** 2))


def optimality_gap(p, A, sup) -> float:
    """The Frank-Wolfe gap of ``A``: an upper bound on ``S(A) - min S``."""
    p = np.asarray(p, np.float64)
    f = np.where(sup, p[:, None] * np.asarray(A, np.float64), 0.0)
    c = np.where(p > 0, (1.0 - p) / np.where(p > 0, p, 1.0), 0.0)
    g = 2.0 * c * f.sum(axis=1)
    cols = sup.any(axis=0)
    best = np.where(sup, g[:, None], np.inf).min(axis=0)
    return float(np.sum(g[:, None] * f) - np.sum(best[cols] * f.sum(axis=0)[cols]))


def solve_numbers(solves) -> dict:
    """Hold each of the system's solves, ``{"A", "p", "adj", "active"}``,
    to the problem:

    * ``alpha_unbiased``: the largest ``|sum_j p_j A[j, i] - 1|`` over the
      origins that have a relay;
    * ``alpha_off_support``: the weight put where it may not go (off the
      closed neighbourhoods, the cohort or the live uplinks, or below 0);
    * ``alpha_excess``: the largest optimality gap as a share of the
      solve's variance, a bound on how far that lies above the least.

    Solves of the same channel are held once."""
    unbiased = off = excess = 0.0
    seen = set()
    for s in solves:
        A = np.asarray(s["A"], np.float64)
        p = np.asarray(s["p"], np.float64)
        active = None if s["active"] is None else np.asarray(s["active"], bool)
        key = (A.tobytes(), p.tobytes(), np.asarray(s["adj"], bool).tobytes(),
               None if active is None else active.tobytes())
        if key in seen:
            continue
        seen.add(key)
        sup = support(p, s["adj"], active)
        cols = sup.any(axis=0)
        if cols.any():
            unbiased = max(unbiased, float(np.max(np.abs(p @ A - 1.0)[cols])))
        off = max(off, float(np.sum(np.abs(A[~sup])) + np.sum(np.maximum(-A[sup], 0.0))))
        got = variance(p, A)
        gap = optimality_gap(p, A, sup)
        if got > 0.0:
            excess = max(excess, gap / got)
    return {"alpha_unbiased": unbiased, "alpha_off_support": off, "alpha_excess": excess}
