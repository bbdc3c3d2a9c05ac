"""Layer ``optimizers``: seconds before XLA sees a module, summed over the step
programs set-up built -- each build record's ``trace_s`` (the loss traced to a
jaxpr, outermost traces only) and ``lower_s`` (the jaxpr lowered to a module),
the two parts printed a program. ``None`` on a program without build records."""

from benchmark import setup_parts


def read(run):
    programs = setup_parts.programs(run)
    if programs is None:
        return None
    for p in programs:
        b = p.build
        print(f"build of {p!r} at step {b.step}: BUILD {b.total_s:.3f} s = trace {b.trace_s:.3f} "
              f"+ lower {b.lower_s:.3f} + compile or load {b.compile_s:.3f} + dispatch "
              f"{b.dispatch_s:.3f}; cache hit {b.cache_hit} (read {b.cache_load_s:.3f} s, "
              f"saved {b.saved_s:.3f} s)")
    return sum(p.build.trace_s + p.build.lower_s for p in programs)
