"""95th percentile over every request (a navigation session) finished in the
window of the time from its start to its result."""
from percentile import nearest_rank


def read(run):
    return nearest_rank([(f.t_done - f.t_start) * 1e3 for f in run.finished], 95)
