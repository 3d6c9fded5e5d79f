"""Device time of the serving engine's prefill module (``jit__prefill``)
per call, in ms."""

MODULE = "jit__prefill"


def read(run):
    m = run.trace.modules.get(MODULE)
    if not m or not m[0]:
        return None
    return 1e3 * m[1] / m[0]
