"""On-chip benchmark of the 3D training executor.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix, per-layer metric or cell sits in a file of its
own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<file>.json``   — a model configuration, as it is run;
* ``bench/archs/<arch>.py``        — the architecture description its
  ``arch`` key names: dims, weight layout, the program's spec, the
  reference's loss and the work the metrics count (``bench/arch.py``);
* ``bench/traffic/<traffic>.json`` — a traffic mix (batch, sequence, pool);
* ``bench/metrics/<metric>.py``    — a per-layer metric's reader;
* ``bench/limits/<cell>.json``     — the limits that decide ``correct``.
"""
