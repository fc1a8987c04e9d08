"""95th percentile over every write of the window of the time from its
enqueue until the refresh that makes it readable returns (refresh cadence
1: the refresh that closes the write's wave)."""
from percentile import nearest_rank


def read(run):
    return nearest_rank([(w.t_end - e[4]) * 1e3 for w in run.waves
                         for e in w.entries if e[0].startswith("w_")], 95)
