import os

# the harness's tests run on the CPU; the chip is never touched here
os.environ.setdefault("JAX_PLATFORMS", "cpu")
