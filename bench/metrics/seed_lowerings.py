"""Programs lowered per traced job under the program's ``entry.seed``
span and the spans inside it: the ``lowerings`` that ``repro.spans``
counts from JAX's lowering events and writes on each span's trace event.
Each one is a jit cache miss, which compiles or loads from the
persistent cache while the device waits."""
from bench import span_reduce


def read(ctx):
    sums = span_reduce.traced(ctx)
    return None if sums is None else sums["seed_lowerings"] / sums["jobs"]
