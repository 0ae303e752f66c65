"""Device: share of the traced window in which no operation ran on the
chip (one minus the union of device operation intervals over the
window)."""


def read(w):
    return 100.0 * (1.0 - w.busy_s / w.window_s)
