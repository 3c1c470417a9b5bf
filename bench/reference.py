"""The plain reference that decides ``correct``: float64 on the host.

A solve is an answer that can be checked on its own.  For each one the
window returned, the reference computes the true relative residual
``||b - A x|| / ||b||`` with the benchmark's own float64 copy of A
(``scipy.sparse``), and reads the solver's exit status.  Nothing here
imports the system under test or takes anything it made but ``x`` and
the status string.

The numbers compared, each against the configuration's limit:

* ``residual`` -- the largest true relative residual over every checked
  solve (limit: ``check.max_true_rel_residual`` in the configuration);
* ``unconverged`` -- solves whose status is not ``CONVERGED``, with a
  non-finite ``x``, or (served traffic) that never came back: limit 0.
"""
from __future__ import annotations

import numpy as np


def true_rel_residual(a, x, b) -> float:
    """``||b - A x|| / ||b||`` in float64 (``a``: scipy CSR, float64)."""
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))


class Check:
    """Running tally of the comparison, one solve at a time."""

    def __init__(self, limit: float):
        self.limit = float(limit)
        self.checked = 0
        self.unconverged = 0
        self.worst = 0.0
        self.failed = 0

    def add(self, a, b, x, status: str) -> bool:
        """Compare one answer; returns whether it passes."""
        self.checked += 1
        rel = true_rel_residual(a, x, b)
        ok_status = status == "CONVERGED" and np.isfinite(rel)
        if not ok_status:
            self.unconverged += 1
        else:
            self.worst = max(self.worst, rel)
        ok = ok_status and rel <= self.limit
        self.failed += not ok
        return ok

    def missing(self, count: int) -> None:
        """Answers that never came back count as unconverged."""
        self.unconverged += count
        self.failed += count

    @property
    def correct(self) -> bool:
        return (self.checked > 0 and self.unconverged == 0
                and self.worst <= self.limit)

    def lines(self) -> dict:
        """Each number compared beside its limit."""
        return {"residual": {"value": self.worst, "limit": self.limit},
                "unconverged": {"value": self.unconverged, "limit": 0}}
