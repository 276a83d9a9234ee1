"""Harness code of the chip benchmark: generator, clients, deployment,
trace reduction, operation and byte counts, verdict."""
