"""chipbench: the chip benchmark of fedml_tpu (BENCHMARK.json at the repo root).

The yardstick lives here: traffic generation, the reduction from traces to
metrics, the table of peaks, the work functions, the plain references and the
comparison that decides `correct`. From the program it takes only the system
under test, its counters and its kernel names.
"""
