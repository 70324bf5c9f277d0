"""The chip benchmark of the federated boosting runtime and its server.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything a cell needs is found by
name: ``configs/<config>.json``, ``traffic/<mix>.json`` (which names a
``drivers/<driver>.py``), ``limits/<cell>.json``,
``metrics/<metric>.py`` per per-layer metric, ``work/<algorithm>_<learner>.py``
and ``peaks.json``.
"""
