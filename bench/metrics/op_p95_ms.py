"""95th percentile over every operation of the window of the time from its
enqueue until the planner flush that resolves it returns."""
from percentile import nearest_rank


def read(run):
    return nearest_rank([(w.t_flush_end - e[4]) * 1e3
                         for w in run.waves for e in w.entries], 95)
