"""Command-line helpers around the benchmark (not run by the driver)."""
