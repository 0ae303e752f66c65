"""Cloud program's share of its roofline, as for the edge program."""


def read(w):
    return w.roofline_pct("cloud")
