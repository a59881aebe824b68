"""On-chip serving benchmark: the cells named in ``BENCHMARK.json``.

Run one cell once with ``python3 benchmarks/onchip/run.py --workload
<name> --seed <n> --seconds <s> --trace <0|1>`` from the root of a
checkout on a machine with a TPU.  Everything a cell needs is found by
name: ``configs/<config>.json``, the architecture module its ``"arch"``
key names, ``archs/<arch>.py`` (spec, weights, reference and least-work
counts; ``model.py`` gives the interface), ``traffic/<mix>.json`` (whose
length histograms are in ``traces/``) and one reader per per-layer metric
in ``metrics/<metric>.py``.
"""
