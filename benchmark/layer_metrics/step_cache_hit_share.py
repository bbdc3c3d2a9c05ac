"""Layer ``runtime``: of the step programs set-up built, the share (%) whose
every compile request the persistent compile cache answered (``cache_hit`` of
the build record): 0 on a first run in a checkout, 100 on a second. ``None``
on a program without build records."""

from benchmark import setup_parts


def read(run):
    builds = setup_parts.builds(run)
    return builds and 100.0 * sum(b.cache_hit for b in builds) / len(builds)
