"""On-chip benchmark of the RT-Gang executor: one cell per run.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs the cell that ``BENCHMARK.json`` names and prints one JSON result line.
Everything a cell needs is found by name under this directory: its
configuration (``configs/<config>.json`` with the glue ``configs/<config>.py``
and the plain reference ``configs/<config>_ref.py``), its traffic mix
(``traffic/<mix>.json``), its best-effort co-runner (``be/<kind>.py``) and
each per-layer metric (``metrics/<name>.py``).
"""
