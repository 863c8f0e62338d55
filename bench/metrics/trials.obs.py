"""Line-search trials per Obs fit."""
from harness.readers import trials as read  # noqa: F401
