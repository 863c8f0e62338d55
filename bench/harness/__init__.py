"""The benchmark's own machinery: data, reference check, trace reduction
and the cell runner.  Imports nothing of the system under test except
through ``program.py``, which drives its public entry points."""
import os

# the TPU runtime writes its logs inside the checkout, not to a fixed
# /tmp path (set before anything imports jax)
os.environ.setdefault("TPU_LOG_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".bench_cache", "tpu_logs"))
