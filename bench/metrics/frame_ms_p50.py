"""Median latency of the window's requests, submit (or due time) to
resolved future, in ms (host clock)."""
from bench import loadgen


def read(run):
    return loadgen.window_metrics(run.window)["frame_ms_p50"]
