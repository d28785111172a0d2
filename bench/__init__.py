"""Chip benchmark of the FPPS registration service (see ``BENCHMARK.json``).

Everything here is the yardstick: traffic generation, the reduction of
profiler traces to metrics, the table of device peaks (and, in
``counts/``, a kernel's operation and byte counts where a cell reads its
roofline), the plain reference and the comparison that decides
``correct``. From the program it takes only the system under
test and its counters. Importing this package imports neither JAX nor
the program, so traffic workers stay off the chip.
"""
