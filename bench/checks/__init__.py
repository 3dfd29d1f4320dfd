"""Correctness checks, one per kind (``"check"`` in a mix's file)."""
