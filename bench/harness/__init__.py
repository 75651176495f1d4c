"""The benchmark harness: cells, traffic, runners, reference, trace reduction."""
