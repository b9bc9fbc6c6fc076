"""On-chip benchmark of the HBMC-ICCG solver.

``python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip and prints one JSON result
line.  Everything that measures (matrices, traffic, metric readers, the
byte yardstick, the trace reduction, the correctness reference) lives in
this package; from the program it takes only the solver under test.
"""
