"""Share of the stream VM's lane-iterations that did useful work.

A batched call runs every lane until its slowest lane stops, so for
each call of the window the lanes cost ``lanes x max(iterations)`` and
did ``sum(iterations)``, from the returned ``CGResult.iterations``.
"""


def read(run):
    calls = run.win.calls
    spent = sum(c["lanes"] * max(c["iterations"]) for c in calls)
    if not spent:
        return None
    return 100.0 * sum(sum(c["iterations"]) for c in calls) / spent
