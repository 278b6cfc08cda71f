"""The window over the frames its one closed-loop client completed in it:
the time per frame over the whole window."""


def read(run):
    done = sum(1 for r in run.requests if run.in_window(r.t_done))
    return run.seconds * 1e3 / done if done else None
