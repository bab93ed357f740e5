"""On-chip benchmark of the live continuum.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything that belongs to
one configuration, traffic mix, cell or per-layer metric is a file of its
own under ``bench/configs``, ``bench/traffic``, ``bench/cells`` and
``bench/metrics``, found by the name ``BENCHMARK.json`` gives it; a
configuration names its model family (``bench/families``) and its plain
reference.
"""
