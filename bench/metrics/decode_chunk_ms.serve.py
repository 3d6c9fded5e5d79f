"""Device time of the serving engine's decode module (``jit__decode``: one
chunk of ``drain_every`` decode steps for every slot) per call, in ms."""

MODULE = "jit__decode"


def read(run):
    m = run.trace.modules.get(MODULE)
    if not m or not m[0]:
        return None
    return 1e3 * m[1] / m[0]
