"""Percent of the traced interval in which the device ran no op (1 minus
the union of its op intervals over the interval), averaged over chips."""
from bench.readers import idle_share as read  # noqa: F401
