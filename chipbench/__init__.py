"""On-chip benchmark of the paged serving path.

``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it is
started on and prints one JSON result as its last line.

Everything here is the yardstick: traffic generation (``traffic``), the
seeded weights (``weights``), the plain float32 reference that decides
``correct`` (``reference``, ``check``), operation and byte counts
(``work``), the peak table (``peaks.json``), the reduction of profiler
traces (``trace``) and the per-layer metric readers (``metrics/``).  From
the program it takes only the system under test: ``ServingEngine`` and its
counters.  Each configuration, traffic mix, cell and per-layer metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it.
"""
