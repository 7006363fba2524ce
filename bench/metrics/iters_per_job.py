"""Mean Lloyd iterations per window job, as ``run_production`` returns
them: how far the Eq. 7 stop lets each fit run."""


def read(ctx):
    iters = ctx["iters"]
    return sum(iters) / len(iters) if iters else None
