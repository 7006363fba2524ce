"""Seconds per traced job in the program's ``entry.seed`` span
(``repro.spans``): the PRNG key and the k-means++ seeding, with the
programs lowered for it."""
from bench import span_reduce


def read(ctx):
    sums = span_reduce.traced(ctx)
    return None if sums is None else sums["seed_s"] / sums["jobs"]
