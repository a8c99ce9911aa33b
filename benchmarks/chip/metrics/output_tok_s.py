"""Output tokens delivered to the host in the window, per second of window."""


def read(run):
    return sum(r.n_in for r in run.requests.values()) / run.window_s
