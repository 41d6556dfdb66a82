"""Tools for building the benchmark on the chip; no run calls them."""
