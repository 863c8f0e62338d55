"""Line-search trials per call."""
from harness.readers import trials as read  # noqa: F401
