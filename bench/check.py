"""The comparison that decides ``correct``.

Each checked answer is a FORA row ``pi_hat`` with its residual mass
``r_sum``. The check holds them to the plain references of
:mod:`reference`, and every answer of the window to FORA's walk budget, by
four numbers, each against its own limit:

- ``max_rel_err``: the worst relative error ``|pi_hat - pi| / pi`` over every
  target with ``pi >= delta = 1/n``. FORA's stated guarantee bounds it by
  epsilon, so the configuration's epsilon is its limit. It covers the push
  and the walks together: the walks carry the residual mass to the targets.
- ``rsum_rel_gap``: the worst relative gap between the program's residual
  mass after the push and that of the float64 synchronous push. The push is
  deterministic, so only rounding separates the two.
- ``mass_gap``: the worst ``|sum(pi_hat) - 1|``. The push conserves mass and
  the walks hand out exactly ``r_sum``, so a row sums to 1 but for rounding;
  a walk phase that drops or doubles its mass shows here.
- ``walks_short``: how many answers of the window drew fewer walk lanes than
  FORA's budget ``ceil(r_sum * omega)``. The guarantee at ``p_f = 1/n``
  holds only with that many walks, so its limit is 0.

The limits of ``rsum_rel_gap`` and ``mass_gap`` are set from readings of
sound runs and of the control (``PERF.md``); all four are kept in each
configuration's file.
"""

from __future__ import annotations

import math

import numpy as np

NAMES = ("max_rel_err", "rsum_rel_gap", "mass_gap", "walks_short")


def numbers(pi_hat: np.ndarray, r_sum: np.ndarray, pi_ref: np.ndarray,
            r_sum_ref: np.ndarray, *, delta: float) -> dict[str, float]:
    """The numbers over a set of answers (rows of ``pi_hat``) that the
    references judge."""
    pi_hat = np.asarray(pi_hat, np.float64)
    pi_ref = np.asarray(pi_ref, np.float64)
    big = pi_ref >= delta
    rel = np.abs(pi_hat - pi_ref)[big] / pi_ref[big]
    return {
        "max_rel_err": float(rel.max()) if rel.size else math.inf,
        "rsum_rel_gap": float(np.max(np.abs(np.asarray(r_sum, np.float64)
                                            - r_sum_ref) / r_sum_ref)),
        "mass_gap": float(np.max(np.abs(pi_hat.sum(axis=1) - 1.0))),
    }


def fora_budget(r_sum: float, omega: float) -> int:
    """FORA's walk count for a residual mass ``r_sum``."""
    return math.ceil(float(r_sum) * omega)


def walks_short(r_sum, lanes, omega: float) -> int:
    """How many answers drew fewer walk lanes than FORA's budget."""
    return sum(int(w) < fora_budget(r, omega) for r, w in zip(r_sum, lanes))


def verdict(values: dict[str, float], limits: dict[str, float]) -> bool:
    """True iff every number is finite and within its limit."""
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in NAMES)


def walk_budget_lines(sources, r_sum, lanes, omega: float) -> list[str]:
    """Each answer's walk lanes against FORA's budget ceil(r_sum * omega)."""
    out = []
    for s, r, w in zip(sources, r_sum, lanes):
        budget = fora_budget(r, omega)
        short = " (under FORA's budget)" if int(w) < budget else ""
        out.append(f"walks: source={int(s)} r_sum={float(r):.6g} "
                   f"walk_lanes={int(w)} fora_budget={budget}{short}")
    return out
