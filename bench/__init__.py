"""The chip benchmark of the SEM-O-RAN re-slice loop (see ``run.py``)."""
