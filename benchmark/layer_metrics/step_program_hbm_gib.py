"""Layer ``optimizers``: what the largest step program holds on a device while
it runs, GiB -- the maximum over set-up's step programs of ``memory()``'s
``resident_bytes`` (arguments + outputs - aliased + temporaries + generated
code, from the executable's ``memory_analysis()``; the parts printed). ``None``
on a program without build records (``memory()`` came with them)."""

from benchmark import setup_parts


def read(run):
    programs = setup_parts.programs(run)
    if programs is None:
        return None
    largest = max((p.memory() for p in programs), key=lambda m: m.resident_bytes)
    print(f"largest step program on a device: {largest.resident_bytes} bytes = arguments "
          f"{largest.argument_bytes} + outputs {largest.output_bytes} - aliased "
          f"{largest.alias_bytes} + temporaries {largest.temp_bytes} + code {largest.code_bytes}")
    return largest.resident_bytes / setup_parts.GIB
