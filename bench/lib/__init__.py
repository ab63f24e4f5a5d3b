"""The benchmark's own yardstick: manifest, data generators, plain
references, trace reduction, peak table and the cell drivers.

Nothing here is imported from the program under test except the system
itself (``repro``), which the drivers call. Everything that decides a
number (traffic, references, reductions, peaks) lives under ``bench/``.
"""
