"""Scheduler: decode tokens over decode steps times slots, in the window:
the share of the batch that decoded."""


def read(run):
    pumps = [p for p in run.pumps if p.in_window]
    steps = sum(p.decode_steps for p in pumps)
    if not steps:
        return None
    return 100.0 * sum(p.decode_tokens for p in pumps) / (steps * run.slots)
