"""The benchmark's yardstick: loading by name, peaks, statistics, FLOP
arithmetic and the trace reduction.  Names no cell, configuration, family,
driver or metric — each is found from the data."""
