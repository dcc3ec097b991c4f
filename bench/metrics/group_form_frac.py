"""Share of the traced window the scheduler spent forming the next wave
(its ``group_form`` spans: Algorithm 3 region groups and steals), by the
host clock of the recorder."""


def read(run):
    t = run.span_seconds("group_form")
    return t / run.window_s if t > 0 else None
