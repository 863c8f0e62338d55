"""Percent of the traced window with no op on the device."""
from harness.readers import idle_share as read  # noqa: F401
