"""The benchmark: cells, metrics, trace reduction and plain references
(see ``harness.py``)."""
