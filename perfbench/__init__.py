"""The chip benchmark of cosmos-curate-tpu (see ``BENCHMARK.json``, ``PERF.md``).

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell once and prints the result as the last line of its
standard output. Everything that belongs to one configuration, one traffic mix
or one per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives it:

    configs/<config>.json          the sizes as run, source, cuts, tolerances
    workloads/<cell>.json          which configuration under which traffic
    traffic/<traffic>.json         parameters read by one general generator
    traffic/<generator>.py         the generators (requests, video corpus)
    drivers/<driver>.py            how a kind of configuration is driven
    layer_metrics/<metric>.py      one reader per per-layer metric
    reference/<model>.py           the plain float32 references
    roofline/                      peaks by device_kind, operations and bytes
    trace_reduce.py                profiler trace -> busy/idle/kernels/gaps

A later PR adds a cell, a configuration or a metric by adding files and
entries; no file here needs an edit for it.
"""
