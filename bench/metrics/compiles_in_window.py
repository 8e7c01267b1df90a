"""Executables the engine compiled during the window (its jit-cache
misses, `RenderEngine.compile_count`); 0 when set-up warmed every shape."""


def read(run):
    return run.compiles_in_window
