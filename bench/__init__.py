"""The on-chip benchmark of the query engine, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON line.  Every piece is found by the name that ``BENCHMARK.json``
gives it:

* a configuration: the file its ``configs`` entry names
  (``bench/configs/<config>.json``), whose ``kind`` names the module in
  ``bench/kinds/`` that makes its data, builds its engine and holds its
  plain reference;
* a traffic mix: ``bench/mixes/<traffic>.json``, parameters that the one
  generator in ``bench/traffic.py`` reads;
* a metric: ``bench/metrics/<name>.py``, a reader with ``read(ctx)``;
* the chip's peaks: ``bench/peaks.json``, keyed by ``device_kind``.

Nothing here holds a cell's name.  The program under test is imported from
``src/``; the benchmark takes from it only the engine, the server, their
result records and counters, and the profiler trace of the run.
"""
