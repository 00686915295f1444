"""fedbench — the benchmark of baton-tpu's FedSim path on the chip.

``python fedbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once; ``BENCHMARK.json`` at the root of
the repo names the cells, configurations and metrics, and
``fedbench/manifest.py`` finds each one's files by name.
"""
