"""Scheduler waves per query (``stats["n_waves"]``, an exact count), split
waves included."""


def read(run):
    if not run.queries:
        return None
    return sum(q.stats["n_waves"] for q in run.queries) / len(run.queries)
