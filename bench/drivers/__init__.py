"""Traffic drivers, one module per kind of traffic, found by name."""
