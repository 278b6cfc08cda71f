"""Seconds from the process's start to the window's: weights, image bank,
server build (int8 calibration), the controller's probe of every bucket,
compilation or the compile cache, and the traffic's warm-up."""


def read(run):
    return run.setup_s
