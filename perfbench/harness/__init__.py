"""The benchmark's own machinery: cells, traffic, traces, peaks, checks.

Nothing here imports the program's metric arithmetic; the program is
reached only through the system modules under `perfbench/systems/`.
"""
