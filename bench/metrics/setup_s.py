"""Seconds from process start to the window's first due time: imports,
device start, weights, engine, warm-up of every shape, traffic."""


def read(run):
    return run.setup_s
