"""The repository's benchmark: one cell of BENCHMARK.json per run.

    python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that decides a number lives under this directory (and its
tests under tests/benchmarks/): traffic generation, the reduction from
traces and spans to metrics, the table of peaks, the analytic counts, the
plain references and the comparison that decides `correct`. From the
program (bigdl_tpu/) it takes only the system under test and its spans,
counters and kernel names. README.md says how to add a cell, a
configuration, a traffic mix or a metric as files.
"""
