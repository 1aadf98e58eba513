"""The benchmark's yardstick: generators, plain reference, work counts, peaks and
the trace reduction. Only ``program.py`` imports the system under test."""
