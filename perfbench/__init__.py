"""The chip benchmark of the relational training path.

``python perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` and prints one JSON
result line. Everything that belongs to one configuration, traffic mix,
cell or metric is a file found by its name:

* ``configs/<config>.json``: the sizes as run, their source, ``reduced``
  and ``assumed``; its ``model`` key names ``models/<model>.py`` (the
  program driven through its user entry points, and the work counted
  from the shapes) and ``reference/<model>.py`` (the plain JAX
  reference, which imports nothing of the program);
* ``traffic/<mix>.json``: the parameters ``feed.Feed`` reads;
* ``tiny/configs/<config>.json``, ``tiny/traffic/<mix>.json``: the keys
  that the CPU tests (``tests/perfbench``) change so that a cell runs at
  a size they hold, laid over the file of the same name; every
  configuration and mix that a cell uses has one, ``{}`` where the file
  runs as it is;
* ``cells/<cell>.json``: the limits of the comparison that decides
  ``correct`` (``check.py``);
* ``metrics/<metric>.py``: one reader per metric of ``BENCHMARK.json``;
* ``peaks.json``: the device peaks, keyed by ``device_kind``.

``models/__init__.py`` gives the contract of a model's module, and the
files a new configuration brings, on one chip or four.
"""
