"""The repo's benchmark: one cell (configuration x traffic) per process.

``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``

Everything that decides a number lives here and nowhere else: traffic
generation, stamping, percentile arithmetic, the peaks table, the FLOP/byte
functions, the trace reduction, the plain reference and the comparison behind
``correct``.  From the program under test the benchmark takes only the
system (``deepspeed_tpu``) and its spans, counters and kernel names.  See
``README.md`` for how a later PR adds a cell with new files only.
"""
