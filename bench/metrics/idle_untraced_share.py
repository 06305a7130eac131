"""Device: share of the traced window in which the device ran no
operation and the program had no span open (``bench/spans.py``), in %:
idle time that no program span accounts for. Nothing is read where the
program opened no span in the window."""
from bench import spans


def read(run):
    att = spans.attribution(run)
    if att is None or att["window_s"] <= 0:
        return None
    return 100.0 * att["untraced_idle_s"] / att["window_s"]
