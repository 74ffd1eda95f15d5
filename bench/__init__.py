"""On-chip serving benchmark for the paged engine (``src/repro/serve``).

``bench/run.py`` runs one cell of ``BENCHMARK.json``: a model
configuration (``bench/configs/<name>.json``) under a traffic mix
(``bench/traffic/<name>.json``), driven through ``ServeEngine.submit``
and ``ServeEngine.run``.  Metrics are readers in ``bench/metrics/``,
one file per metric, found by the metric's name, and each
configuration names its plain float32 reference, which also gives the
weights' layout (``bench/references/<name>.py``).  Everything the
yardstick needs (traffic generation, weights, the references, the
trace reduction, the peak table, FLOP and byte counts) lives in this
package; from the program it takes only the engine, its
``ServeStats`` counters and the names of its XLA programs and kernels.
"""
