"""Programs traced and lowered inside the measured window (JAX's own
lowering events, counted by the harness); each is a compile or a
compile-cache load on the timed path. Should be 0."""


def read(ctx):
    return float(ctx.log.compiles_in_window)
