"""The chip benchmark's yardstick: manifest, peaks, FLOP counts, trace
reduction, the traffic generator and the plain references.

Nothing here imports the program under test (``repro``); the drivers
under ``benchmarks/chip/drivers/`` are the only modules that do.
"""
