"""``overwork``: vertices the searches expanded over the vertices they
reached, summed over the window (the program's work counter,
``execute``'s ``info["work"]``; 1 is a level-synchronous BFS, more is
re-expansion after a vertex's distance improved)."""


def read(run):
    reached = sum(job["reached"] for job in run.jobs)
    if not reached:
        return None
    return sum(job["work"] for job in run.jobs) / reached
