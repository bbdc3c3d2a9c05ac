"""Layer ``runtime``: seconds in ``bf.init()`` -- mesh, compile cache, and on a
fresh checkout the build of the native runtime. A harness span."""


def read(run):
    seconds = run.spans.seconds.get("init_s")
    return seconds[0] if seconds else None
