"""Whole step: the least compute time of the window's work (the model
operations of every prompt prefilled and every token decoded in the
window, each matmul at the peak of the dtype its weight is served in,
``work.py``) over the window."""


def read(run):
    if run.peaks is None:
        return None
    w = run.work
    ops = None
    for p in run.pumps:
        if not p.in_window:
            continue
        for n in p.prompt_tokens:
            ops = w.prompt_ops(n) if ops is None else ops + w.prompt_ops(n)
    least = ops.seconds(run.peaks) if ops is not None else 0.0
    for s in run.served:
        n = len(s.spec.prompt)
        for i, t in enumerate(s.times[1:], start=2):
            if run.w0 <= t <= run.w1:
                least += w.token_ops(n + i - 1).seconds(run.peaks)
    return 100.0 * least / run.window_s if least > 0 else None
