"""The benchmark of acco-tpu: ``python benchmark/run.py --workload <cell> ...``.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: the window arithmetic, the reduction from traces and spans
to metrics, the table of peaks, the FLOP and byte counts, the plain reference
and the comparison that decides ``correct``. From the program it takes the
entry point users call (``main.run``), its spans, counters and kernel names.
"""
