"""Chip benchmark of DV-DVFS jobs over HDFS-size blocks.

``python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on one TPU chip: a
closed loop of DV-DVFS jobs (estimate, run every block, plan, account) for
``--seconds``, then checks the window's outputs against plain NumPy
references and prints one JSON result line.

Everything that belongs to one configuration, traffic mix, app, dataset kind
or metric sits in a file named after it, which the harness finds by that
name (see ``cells.py``).
"""
