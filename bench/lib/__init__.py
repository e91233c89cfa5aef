"""The benchmark's yardstick: generators, reference, comparison, peaks,
operation counts and the trace reduction. Nothing here imports the program
except `train.py`, which drives it."""
