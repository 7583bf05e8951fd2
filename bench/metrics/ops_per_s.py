"""ops_per_s: ops completed over the whole window, per second of it
(host clock, from the first commit's start to the last one's end)."""


def read(run):
    if run.commits is None or not len(run.commits) or run.window_s <= 0:
        return None
    return float(run.commits[:, 3].sum() / run.window_s)
