"""The chip benchmark of this repository: one command, cells found by name.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

Everything that measures lives here, apart from the system under test:
traffic generation (:mod:`bench.traffic_gen`), the candidate table the
fleet cells run (:mod:`bench.profiles`), the plain references that
decide ``correct`` (:mod:`bench.alert_ref`, :mod:`bench.lm_ref`), model
FLOP arithmetic, the trace reduction (:mod:`bench.xtrace`) and the table
of peaks (``peaks.json``).  Configurations, traffic mixes, drivers and
metric readers are files of their own under ``configs/``, ``traffic/``,
``drivers/`` and ``metrics/``, found by the names in ``BENCHMARK.json``.
"""
