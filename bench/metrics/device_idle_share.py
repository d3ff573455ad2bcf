"""``device_idle_share``: 1 - busy / window, in %, over the traced window
(first search's span start to the last one's end), averaged over chips."""
from bench import trace


def read(run):
    if run.profile is None or not run.profile.ops or run.window_ns is None:
        return None
    lo, hi = run.window_ns
    return 100.0 * (1.0 - trace.busy_ns(run.profile.ops, lo, hi) / (hi - lo))
