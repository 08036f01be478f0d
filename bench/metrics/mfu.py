"""Model FLOP/s utilisation of the whole step: the operations the traced
interval's served tokens need, over the device-busy seconds of every chip
at the bf16 peak, in percent."""
from bench.readers import mfu as read  # noqa: F401
