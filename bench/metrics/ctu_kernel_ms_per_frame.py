"""Device time of the Pallas PRTU (CTU) kernel per served frame of the
window, in ms (from the profiler trace; the kernel by its Mosaic name)."""
KERNEL = "_prtu_entry_kernel"


def read(run):
    frames = len(run.served)
    if run.trace is None or not frames:
        return None
    seconds = run.trace.op_seconds(kernel=KERNEL)
    return 1e3 * seconds / frames if seconds > 0 else None
