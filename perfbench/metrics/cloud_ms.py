"""Split executor, cloud program (trunk after the cut and the action
head): device time per call, from the device trace."""


def read(w):
    return 1e3 * w.program_mean_s("cloud")
