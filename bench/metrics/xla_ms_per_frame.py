"""Device time of every op that is not a Pallas kernel, per served frame
of the window, in ms: preprocess, Stage-1 compaction, the CTU's and the
blend's XLA glue, finalize (from the profiler trace)."""


def read(run):
    frames = len(run.served)
    if run.trace is None or not run.trace.ops or not frames:
        return None
    return 1e3 * run.trace.op_seconds(kernel="") / frames
