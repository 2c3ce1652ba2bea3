"""The chip benchmark: one cell (a model configuration under a traffic mix)
per run of ``python3 bench/run.py``; see ``bench/README.md``."""
