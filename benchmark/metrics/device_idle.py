"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-operation intervals) / window, mean over chips."""


def read(run, params):
    r = run.reduced
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
