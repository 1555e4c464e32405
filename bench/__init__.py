"""On-chip benchmark of the RCLL SPH solver (see ``bench/run.py``)."""
