"""On-chip benchmark of the PPR service's served query path (``run.py``)."""
