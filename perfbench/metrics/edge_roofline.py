"""Edge program's share of its roofline: the least time of each traced
call (the larger of its FLOPs over the peak rate and its bytes over the
peak bandwidth, from the benchmark's cost functions) over the call's
device time, summed over the window's calls."""


def read(w):
    return w.roofline_pct("edge")
