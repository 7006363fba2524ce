"""Programs lowered inside the measured window: each one compiles or loads
from the persistent cache while jobs wait.  Set-up warms every shape, so
this reads 0 unless the timed path makes a new program."""


def read(ctx):
    return ctx["window_compiles"]
