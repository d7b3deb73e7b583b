"""qps: queries answered in the window over the window's seconds (host
clock; the window ends with the last batch's answer on the host)."""


def read(run):
    if run.window_s <= 0:
        return None
    return sum(b.queries for b in run.batches) / run.window_s
