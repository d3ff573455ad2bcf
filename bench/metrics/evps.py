"""``evps``: LDBC Graphalytics' edges-plus-vertices per second.

(|V| + |E|) of the graph for every search of the window, with |E| the
undirected edges, over the searches' summed wall time (host clock, each
from the call to the wait on its result)."""


def read(run):
    if not run.jobs:
        return None
    size = run.n + run.m // 2
    return size * len(run.jobs) / sum(job["wall_s"] for job in run.jobs)
