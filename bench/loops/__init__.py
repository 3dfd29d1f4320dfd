"""Request loops, one per kind of traffic (``"loop"`` in a mix's file)."""
