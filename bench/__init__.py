"""Chip benchmark of the serving engine, driven by ``BENCHMARK.json``.

Run one cell as ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the checkout root.  Everything that
belongs to one configuration, traffic mix, cell or per-layer metric is a
file of its own, found by name:

* ``bench/configs/<config>.json``   model sizes, policy, cuts, departures
* ``bench/traffic/<traffic>.json``  arrival and length parameters, engine
  capacity sized to them
* ``bench/cells/<cell>.json``       the limit that decides ``correct``
* ``bench/layer_metrics/<metric>.py``  one reader per per-layer metric

This package imports nothing of the program at module level; the serving
engine is imported by :mod:`bench.harness` when a run builds it.
"""
