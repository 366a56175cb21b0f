"""Drivers: each builds the system under test for one kind of cell, warms it,
drives the measured window and hands the benchmark what it produced."""
