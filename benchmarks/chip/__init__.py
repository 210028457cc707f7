"""On-chip benchmark of the field store: one cell of ``BENCHMARK.json`` per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: a cell's configuration file, its traffic mix
(``traffic/<name>.json``), the generator module that the mix names
(``generators/<name>.py``, ``fields`` by default) and each per-layer
metric's reader (``metrics/<name>.py``).  The yardstick lives here too:
the field makers, the traffic generators, the numpy reference quantiser
and the comparison that decides ``correct``, the table of chip peaks and
the reduction of a profiler trace to device time.
"""
