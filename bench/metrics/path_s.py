"""Seconds per whole lam1 path through fit_path."""
from harness.readers import seconds_per_call as read  # noqa: F401
