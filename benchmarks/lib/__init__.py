"""The yardstick all families share: traffic, the comparisons of
``correct``, the roofline and the peaks, the trace reduction, the two
drivers. What is a model's own (weights, plain reference, work counts)
is in ``benchmarks/families/<family>/``, found by the name in the
configuration file (``spec.py``). Nothing here imports the program under
test except ``program.py`` and the two drivers, which call its entry
points."""
