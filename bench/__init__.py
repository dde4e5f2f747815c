"""The chip benchmark of the integer training and serving system.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` once on the accelerator it is
started on and prints one JSON result line.  Everything here is the
yardstick: traffic generation, the plain float32 reference, the
comparison that decides ``correct``, the trace reduction, the peaks
table and the operation and byte counts.  From ``src/repro`` it takes
only the system under test.
"""
