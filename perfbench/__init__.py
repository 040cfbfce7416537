"""The chip benchmark of the autoscaling simulator (see README.md)."""
