"""One driver per program entry point, found by the cell's ``entry``."""
