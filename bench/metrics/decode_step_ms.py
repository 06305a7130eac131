"""Model step: device milliseconds per call of the decode step's
executable (``jit_step``, ``repro.serving.engine._paged_step``) in the
traced window."""

EXECUTABLE = "jit_step"


def read(run):
    trace = run["trace"]
    calls, secs = (trace or {}).get("executables", {}).get(EXECUTABLE, (0, 0.0))
    if not calls:
        return None
    return 1e3 * secs / calls
