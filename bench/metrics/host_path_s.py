"""Host seconds per traced job: the program's ``entry.job`` span less its
``entry.wait`` (``repro.spans``), the part of a job in which the device
waits for the host: transfer, seeding, config, dispatch, read-back."""
from bench import span_reduce


def read(ctx):
    sums = span_reduce.traced(ctx)
    if sums is None:
        return None
    return (sums["job_s"] - sums["wait_s"]) / sums["jobs"]
