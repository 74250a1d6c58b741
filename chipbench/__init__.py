"""The benchmark of this repository: one command runs one cell once on the
chip (``python3 -m chipbench.run --workload <cell> ...``). See README.md."""
