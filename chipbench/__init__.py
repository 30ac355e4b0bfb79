"""The on-chip benchmark: ``python3 -m chipbench.run --workload <cell> ...``
(see ``chipbench/README.md``)."""
