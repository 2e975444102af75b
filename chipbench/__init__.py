"""On-chip benchmark of the approximate-arithmetic system.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on.  Every piece of a cell is found by its name: the
configuration in ``configs/<config>.json`` (with its plain reference
in ``configs/<config>.py``), the system it names in
``system/<system>.py``, the traffic mix in ``mixes/<traffic>.json``,
the loop it names in ``loop/<loop>.py`` and each per-layer metric's
reader in ``metrics/<metric>.py`` (see ``cells.py``).  Nothing here is
imported by the system under test.
"""
