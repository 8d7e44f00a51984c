"""Plain references the benchmark checks the system against."""
