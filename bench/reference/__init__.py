"""Plain float64 reference of the mapping problem and its searches."""
