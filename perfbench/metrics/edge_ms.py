"""Split executor, edge program: device time per call, from the
program's executions in the device trace."""


def read(w):
    return 1e3 * w.program_mean_s("edge")
