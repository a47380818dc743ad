"""Model step: device-busy time inside decode-only pumps, per pump."""


def read(run):
    t = run.trace
    if t is None or not t.count_by_kind.get("decode"):
        return None
    return 1e3 * t.busy_by_kind["decode"] / t.count_by_kind["decode"]
