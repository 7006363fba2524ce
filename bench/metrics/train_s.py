"""Seconds of set-up spent fitting the stop model
(``repro.launch.cluster.fit_stop_model``: harvest, regression, h*)."""


def read(ctx):
    return ctx["train_s"]
