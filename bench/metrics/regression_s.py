"""Seconds of set-up in the program's ``stop.regression`` span
(``repro.spans`` totals): h(r) fitted to the harvest, the family chosen
and h* derived, the part of ``train_s`` after ``harvest_s``."""


def read(ctx):
    del ctx
    try:
        from repro import spans
    except ImportError:
        return None
    regression = spans.totals().get("stop.regression")
    return None if regression is None else regression["seconds"]
