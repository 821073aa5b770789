"""The chip benchmark of the chordless-cycle service: one harness, data per cell.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the chips of this machine and
prints one JSON result line. See ``bench/run.py`` for the layout of a cell.
"""
