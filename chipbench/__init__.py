"""On-chip benchmark of the Gopher temporal-graph platform.

``chipbench/run.py`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything a cell is made of
is found by name: ``configs/<config>.json`` (the deployment),
``traffic/<traffic>.json`` (the load), ``checks/<analytic>.py`` (the plain
reference and the comparison that decides ``correct``) and
``metrics/<metric>.py`` (one reader per per-layer metric).
"""
