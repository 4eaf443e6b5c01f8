"""The chip benchmark: data-driven cells of configurations and traffic
mixes, run one at a time on the chip by ``run.py``."""
