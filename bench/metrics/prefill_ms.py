"""Prefill fold: host milliseconds per ``adapter.insert`` (which ends with
the first token on the host), total over the traced admissions divided by
their number."""


def read(run):
    pre = run["counters"].get("prefills")
    if not pre:
        return None
    return 1e3 * sum(dt for dt, _, _ in pre) / len(pre)
