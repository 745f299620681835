"""The chip benchmark: see chipbench/harness.py and PERF.md."""
