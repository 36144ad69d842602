import os

# the benchmark's own tests run on the CPU (``python -m pytest bench/tests``)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
