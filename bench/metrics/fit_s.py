"""Seconds per ConcordEstimator.fit call, host work included (one chip)."""
from harness.readers import seconds_per_call as read  # noqa: F401
