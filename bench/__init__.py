"""On-chip benchmark of the partition -> process path (see ``run.py``)."""
