"""On-chip benchmark of the experiment engines, driven by data.

``BENCHMARK.json`` at the repository root names the cells; each cell is
one configuration (``configs/<name>.json``) under one traffic mix
(``traffic/<name>.json``), with the limits of its correctness check in
``limits/<workload>.json``.  Each per-layer metric is a reader of its own
in ``metrics/<name>.py``.  The harness finds all of them by name, so a
new cell or metric is a new file and never an edit.

    python3 -m chipbench.run --workload k50_pair --seed 7 --seconds 30 --trace 0
"""
