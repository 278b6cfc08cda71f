"""Real images over bucket slots, in percent, over the micro-batches
dispatched in the window."""


def read(run):
    ds = [d for d in run.dispatches if run.in_window(d[0])]
    slots = sum(d[2] for d in ds)
    return 100.0 * sum(d[3] for d in ds) / slots if slots else None
