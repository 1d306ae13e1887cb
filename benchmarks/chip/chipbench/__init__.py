"""The chip benchmark's harness: data, reference, comparison, trace
reduction and operation counts. ``benchmarks/chip/run.py`` is the entry;
configurations, traffic mixes and per-layer metric readers are files of
their own beside this package, found by name."""
