"""``wavefront_fill``: share of the wavefront's slots that popped a task,
summed over the window: sum(pops) / (rounds x wavefront), in %, from the
program's counters (``execute``'s ``stats.items_processed`` and
``info["rounds"]``)."""


def read(run):
    if not run.jobs:
        return None
    rounds = sum(job["rounds"] for job in run.jobs)
    return 100.0 * sum(job["pops"] for job in run.jobs) / (
        rounds * run.wavefront)
