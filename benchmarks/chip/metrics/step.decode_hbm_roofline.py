"""Step program: the fewest bytes the window's decode-only steps had to
read (``work.py``: the weights they need, the experts their tokens reach,
the live KV of their sequences), at the chip's HBM bandwidth, over the
device-busy time of those steps."""


def read(run):
    t = run.trace
    if t is None or run.peaks is None:
        return None
    pumps = [p for p in run.pumps if p.in_window]
    need, busy = 0.0, 0.0
    for p, b in zip(pumps, t.busy_in_pumps):
        if p.kind == "decode" and p.decode_tokens and b > 0:
            need += run.work.decode_bytes(p.decode_tokens, p.keys)
            busy += b
    if busy <= 0:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / busy
