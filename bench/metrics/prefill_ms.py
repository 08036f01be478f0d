"""Length of one prefill, from its padded batch to its first tokens on
the host (the ``engine.prefill`` span), median over the prefills inside
the traced interval."""
from bench.spans import prefill_ms as read  # noqa: F401
