"""Length of one decode step, from the chunk's state being built to its
tokens on the host (``engine.readback`` end minus ``engine.decode``
start), median over the engine steps inside the traced interval."""
from bench.spans import decode_step_ms as read  # noqa: F401
