"""Seconds per ConcordEstimator.fit call of the Obs cell, host work included."""
from harness.readers import seconds_per_call as read  # noqa: F401
