"""Share of the traced training window in which no op ran on the device,
in %: 1 - busy / window."""


def read(run):
    t = run.trace
    if not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
