"""CPU tests of the chip benchmark: its files, generators, reference,
operation counts, trace reduction and the faults `correct` must catch."""
