"""Device idle inside one engine step: the ``engine.step`` span's length
minus the device-busy time inside it (union of leaf ops, mean over
chips), median over the steps inside the traced interval."""
from bench.spans import step_host_gap_ms as read  # noqa: F401
