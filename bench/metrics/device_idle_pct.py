"""Share of the window in which no operation ran on the device.

``1 - busy / window`` from the trace: busy is the union of the device
operations' intervals inside the window span (``bench/tracefile.py``).
Each tagged entry (``device_idle_pct.<tag>``) reads it for the cells
whose end-to-end metric it moves.
"""
from bench.tracefile import idle_pct


def read(run):
    return idle_pct(run.trace)
