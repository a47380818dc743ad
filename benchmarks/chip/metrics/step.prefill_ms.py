"""Model step: device-busy time inside pumps that ran a prefill, per
pump."""


def read(run):
    t = run.trace
    if t is None or not t.count_by_kind.get("prefill"):
        return None
    return 1e3 * t.busy_by_kind["prefill"] / t.count_by_kind["prefill"]
