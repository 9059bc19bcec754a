"""Seconds from process start to the window's start: weights, planner
prewarm, compilation or cache loads, and warm-up."""


def read(run):
    return run.setup_s
