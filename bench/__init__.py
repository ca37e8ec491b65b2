"""The chip benchmark of the served MaxSim cascade (see ``bench/run.py``)."""
