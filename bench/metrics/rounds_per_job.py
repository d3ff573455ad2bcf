"""``rounds_per_job``: drive-loop rounds per search, as ``execute``
reports them (``info["rounds"]``), averaged over the window."""


def read(run):
    if not run.jobs:
        return None
    return sum(job["rounds"] for job in run.jobs) / len(run.jobs)
