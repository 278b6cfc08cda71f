"""Images whose logits reached the client inside the window, per second
of the window."""


def read(run):
    done = sum(1 for r in run.requests if run.in_window(r.t_done))
    return done / run.seconds
