"""The chip benchmark's harness: cells, traffic, metrics and the
comparison that decides `correct`, all found by name under `bench/`."""
