"""Output tokens delivered to the client in the window, over the window's
length."""


def read(run):
    n = sum(1 for s in run.served for t in s.times if run.w0 <= t <= run.w1)
    return n / run.window_s
