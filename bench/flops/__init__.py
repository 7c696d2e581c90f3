"""Analytic operation and byte counts, kept with the benchmark."""
