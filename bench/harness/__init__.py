"""The benchmark harness: shared code of bench/run.py."""
