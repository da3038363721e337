"""The benchmark's shared code: spec lookup, serving driver, records."""
