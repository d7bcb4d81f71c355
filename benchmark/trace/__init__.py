"""Device trace: capture (``capture.py``) and reduction (``reduce.py``)."""
