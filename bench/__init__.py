"""The chip benchmark of the serving stack: ``python bench/run.py --help``."""
