"""Residual replacements per solve.

The window's ``replacements`` counter (the program's solver metrics:
one per lane whose recurrence residual was replaced by ``b - A x`` on
the device) over the solves the window returned.  A program that
replaces no residual keeps no such counter, and this reads nothing.
"""


def read(run):
    reps = run.win.counters.get("replacements")
    if reps is None or not run.win.answers:
        return None
    return reps / len(run.win.answers)
