"""Seconds of set-up in the program's ``stop.harvest`` span
(``repro.spans`` totals): the stop model's training fits and their (r, h)
traces, the part of ``train_s`` before the regression."""


def read(ctx):
    del ctx
    try:
        from repro import spans
    except ImportError:
        return None
    harvest = spans.totals().get("stop.harvest")
    return None if harvest is None else harvest["seconds"]
