"""The benchmark harness: one general driver per kind of traffic, the
plain reference, the trace reduction and the yardstick of peaks and
byte counts.  ``bench/run.py`` is its entry point."""
