"""The on-chip benchmark of dgc-tpu (BENCHMARK.json names this directory).

Everything the yardstick needs lives here: the cell loader, the traffic
generator, the round arithmetic, the correctness reference, the trace
reduction, the peaks table and one small reader per per-layer metric. From
the program it takes only the system under test (the objects ``train.py``
builds) and its ``dgcph.*`` scopes and kernel names.
"""
