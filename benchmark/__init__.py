"""The on-chip benchmark of nnstreamer_tpu: harness, yardstick and data.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the stamps, the reduction from the
profiler's trace, the table of peaks, the work functions, each
configuration's plain reference and the comparison behind ``correct``.
From the program it takes the system under test and its hooks only.

A cell, a configuration, a traffic mix or a per-layer metric is added by
new files and new ``BENCHMARK.json`` entries alone; see ``manifest.py``.
"""
