import os

# the benchmark's tests run on the CPU at small sizes; only bench/run.py's
# own look for a chip is steered, by the tests that exercise it
os.environ.setdefault("JAX_PLATFORMS", "cpu")
