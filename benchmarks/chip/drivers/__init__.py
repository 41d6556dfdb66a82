"""Drivers: one per kind of traffic, each with setup / window /
release / check."""
