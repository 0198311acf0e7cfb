"""The benchmark: the yardstick later PRs are measured with and may not edit.

Everything that decides a number lives here, not in the program: traffic
generation (loadgen.py), the reduction from a profiler trace to metrics
(trace.py), the table of device peaks (peaks.json), the functions that count
a model's or a kernel's operations and bytes (flops.py), a plain float32
reference of each configuration (reference/), and the comparisons that decide
`correct` (correct.py). From the program it takes only the system under test,
its counters and its kernel names. PERF.md section 3 says how a cell's files
are found from BENCHMARK.json.
"""
