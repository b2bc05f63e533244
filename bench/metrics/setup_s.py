"""Seconds from process start to the opening of the measured window:
imports, data, engine, compilation (or compile-cache loads) and warm-up."""


def read(run):
    return run.setup_s
