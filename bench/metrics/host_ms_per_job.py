"""``host_ms_per_job``: milliseconds of each search's span in which the
device ran nothing (host span minus device-busy time inside it: building
the program, ``execute``'s trace and cache load, reading the result),
averaged over the window."""
from bench import trace


def read(run):
    if run.profile is None or not run.profile.ops:
        return None
    spans = run.job_spans()
    if not spans:
        return None
    idle = sum(s.dur_ns - trace.busy_ns(run.profile.ops, s.start_ns,
                                        s.end_ns) for s in spans)
    return idle / 1e6 / len(spans)
