"""``round_ms``: device-busy milliseconds per round: the union of device
operations inside the searches' spans over their rounds (device trace)."""
from bench import trace


def read(run):
    if run.profile is None or not run.profile.ops:
        return None
    spans = run.job_spans()
    if len(spans) != len(run.jobs):
        return None
    busy = sum(trace.busy_ns(run.profile.ops, s.start_ns, s.end_ns)
               for s in spans)
    return busy / 1e6 / sum(job["rounds"] for job in run.jobs)
