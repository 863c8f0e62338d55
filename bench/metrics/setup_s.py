"""Process start to the first timed call."""
from harness.readers import setup_seconds as read  # noqa: F401
