"""On-chip serving benchmark of the BLAST engine (see run.py)."""
