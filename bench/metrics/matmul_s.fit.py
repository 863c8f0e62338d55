"""Device seconds per call in matrix-product ops."""
from harness.readers import matmul_seconds as read  # noqa: F401
