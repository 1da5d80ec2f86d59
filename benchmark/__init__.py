"""The repository's benchmark: one cell, one run, one process.

``python -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` — see ``benchmark/README.md``. Everything that decides a
number lives in this directory: traffic generation, the plain reference,
the comparison behind ``correct``, the reduction from trace and counters
to metrics, the table of peaks and the FLOP/byte counts. From
``sitewhere_tpu`` it takes the system under test, its counters and its
kernel names — nothing else.
"""
