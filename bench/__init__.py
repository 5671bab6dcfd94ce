"""The chip benchmark of this repository: ``python3 bench/run.py --help``."""
