"""Median host time of one ``jpcg_solve_batched`` call's preparation, in ms.

The program's own ``batch.prepare`` span (``core/batch.py``): the layout
choice, the packing of every lane into the stacked operand
(``batch.pack``) and the transfer of the operands to the device
(``batch.put``), one span per call.  Loading this reader switches the
program's span recorder on (``bench/programtrace.py``).
"""
import statistics

from bench import programtrace

programtrace.arm()


def read(run):
    ms = [(s.end_ns - s.start_ns) / 1e6
          for s in programtrace.view_of(run).spans
          if s.name == "batch.prepare"]
    return statistics.median(ms) if ms else None
