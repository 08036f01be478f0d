"""How long a request's first token, picked at the end of its prefill,
waits to reach the caller: the end of the Router poll that holds the
``engine.prefill`` span minus that span's end, median over the prefills
ending in the traced interval (program spans on the device trace)."""
from bench.spans import first_token_hold_ms as read  # noqa: F401
