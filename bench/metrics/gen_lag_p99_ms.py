"""How late the load generator submitted the window's requests against
their open-loop schedule, 99th percentile, host clock."""
from bench.readers import gen_lag_p99_ms as read  # noqa: F401
