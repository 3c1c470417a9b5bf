"""Tail of a request's stay inside ``SolverEngine``, in s.

The nearest-rank 90th percentile of the program's ``engine.request``
records (``serve/solver_engine.py``): from the start of a request's
first admission to the harvest that returns it, so admission, the ticks
it waits through and its harvest, and not the generator's backlog.
Loading this reader switches the program's span recorder on
(``bench/programtrace.py``).
"""
from bench import programtrace
from bench.loadgen import percentile

programtrace.arm()


def read(run):
    secs = [(s.end_ns - s.start_ns) / 1e9
            for s in programtrace.view_of(run).spans
            if s.name == "engine.request"]
    return percentile(secs, 0.90) if secs else None
