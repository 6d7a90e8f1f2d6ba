"""Plain float32 references, written from the published descriptions, one
family a file under `families/`.

They import nothing of the program and take nothing it has made: weights
come from the seed through `benchmark/harness/weights.py`, one layer at a
time, and every matmul runs at `highest` precision.
"""
