"""On-chip benchmark of the FourierFT system: `python3 bench/run.py --help`."""
