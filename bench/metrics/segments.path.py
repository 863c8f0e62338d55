"""Chunk programs launched per batched path."""
from harness.readers import segments as read  # noqa: F401
