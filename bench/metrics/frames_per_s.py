"""Frames completed in the window per second of the window (host clock)."""
from bench import loadgen


def read(run):
    return loadgen.window_metrics(run.window)["frames_per_s"]
