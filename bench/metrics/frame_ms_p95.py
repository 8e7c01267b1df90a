"""95th percentile (nearest rank) of the latency of every request of the
window, in ms (host clock)."""
from bench import loadgen


def read(run):
    return loadgen.window_metrics(run.window)["frame_ms_p95"]
