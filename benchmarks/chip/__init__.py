"""On-chip benchmark of the SEAFL server and FL loop (see run.py)."""
