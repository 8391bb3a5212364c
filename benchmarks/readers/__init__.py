"""Per-layer metric readers of their own: ``<metric>.py: read(ctx)``,
loaded by path from ``lib/readers.py``; shared code sits beside them."""
