"""The chip benchmark's yardstick: cell and configuration loading, the
graph generator, work counts, the plain reference and its comparison, and
the reduction of a device trace to per-layer metrics. Nothing here imports
the program except `job`, which drives it."""
