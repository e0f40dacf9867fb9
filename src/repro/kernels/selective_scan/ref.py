"""Oracle: the model's chunked ``lax.scan`` implementation."""
from repro.models.ssm import _chunked_scan as selective_scan_ref  # noqa: F401
