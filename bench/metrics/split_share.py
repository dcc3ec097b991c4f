"""Share of waves that overflowed and were split and run again:
``overflow_retries / n_waves`` over the window's queries."""


def read(run):
    waves = sum(q.stats["n_waves"] for q in run.queries)
    if not waves:
        return None
    return sum(q.stats["overflow_retries"] for q in run.queries) / waves
