"""The chip benchmark: closed-loop agent fleets served through the engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Everything that measures (traffic
generation, weights, the plain reference, the trace reduction, peaks, FLOP and
byte counts, metric readers) lives here; from the program the benchmark takes
only the engine it drives.
"""
