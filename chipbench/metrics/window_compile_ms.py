"""Wall milliseconds the server spent compiling its pool executables inside
the window: its own ``compile_ms`` counter, differenced over the window
(exact, where ``compiles_in_window`` reads a ring of events that can let
compile events go)."""
from chipbench import admit_trace


def read(run):
    return admit_trace.window_compile_ms(run)
