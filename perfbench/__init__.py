"""On-chip benchmark of the packed CIM deploy path (see PERF.md).

``python perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix, cell or per-layer metric is a
file of its own that the harness finds by name:

- ``configs/<config>.json``: the sizes as run; ``configs/<config>.py``:
  builds the system under test and counts its work;
  ``configs/<config>.reference.py``: the plain reference (imports nothing
  of the program).
- ``traffic/<mix>.json``: the parameters the general generator
  (``traffic.py``) and the unit of work read.
- ``limits/<cell>.json``: the correctness sample and each compared
  number's limit.
- ``metrics/<metric>.py``: one per-layer metric's reader.
"""
