"""Mean Rand index of the early stop against the full-convergence run
over the checked jobs: the accuracy the stop model certified (r*), with
its margin."""


def read(ctx):
    rands = ctx["rands"]
    return sum(rands) / len(rands) if rands else None
