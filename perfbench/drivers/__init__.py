"""One driver per kind of configuration (its ``driver`` key)."""
