"""The benchmark's own yardstick: cell loader, clock and statistics,
device and peak table, trace reduction, FLOP count, model building and
the logit comparison.  Nothing here names a cell, a configuration, a
traffic mix, a kind or a layer metric: those are files found by name
(see ``cells.py`` and ``benchmarks/README.md``)."""
