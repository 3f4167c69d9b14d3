"""Paged cache: prompt tokens whose prefill a prefix hit skipped, as a
share of the prompt tokens admitted in the traced window, %."""


def read(run):
    pre = run["counters"].get("prefills")
    if not pre:
        return None
    total = sum(n for _, n, _ in pre)
    return 100.0 * sum(s for _, _, s in pre) / total
