"""Chip benchmark of the training and serving paths on a TPU.

``python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` (one model configuration under one
traffic mix) in one process and prints one JSON result line last.  The
harness is driven by data: a configuration is ``configs/<name>.json``, a
traffic mix ``traffic/<name>.json``, a per-layer metric a reader
``metrics/<name>.py`` and a cell's correctness limits
``limits/<workload>.json``; each is found by its name.
"""
