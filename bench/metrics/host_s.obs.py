"""Facade host seconds per Obs fit: wall time minus the reported solve time."""
from harness.readers import host_seconds as read  # noqa: F401
