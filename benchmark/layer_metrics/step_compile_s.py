"""Layer ``runtime``: seconds the backend took for the step programs set-up
built, summed -- each build record's ``compile_s``: XLA's compilation, or with
``cache_hit`` the persistent cache's load of the executable. ``None`` on a
program without build records."""

from benchmark import setup_parts


def read(run):
    builds = setup_parts.builds(run)
    return builds and sum(b.compile_s for b in builds)
