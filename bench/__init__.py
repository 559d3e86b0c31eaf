"""The on-chip benchmark: harness, traffic generator, plain reference and
trace reduction.  ``python3 bench/run.py --help`` runs one cell."""
