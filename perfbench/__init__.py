"""On-chip benchmark of the sparse allreduce system (see PERF.md).

Everything that decides a number lives here: traffic generation, the plain
references, the peak table, the needed-bytes functions and the reduction of
profiler traces.  From the program under test (``src/repro``) the benchmark
takes only the entry points it times and their counters.
"""
