"""Harness for the simulator benchmark; see PERF.md."""
