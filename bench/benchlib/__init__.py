"""The chip benchmark's own library: cell specs found by name, data and
traffic generation, the plain reference, the windows that drive the two entry
points, and the reduction from device traces to metrics."""
