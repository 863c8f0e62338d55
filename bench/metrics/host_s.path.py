"""Facade host seconds per call: wall time minus the reported solve time."""
from harness.readers import host_seconds as read  # noqa: F401
