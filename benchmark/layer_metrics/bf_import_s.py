"""Layer ``runtime``: seconds ``import bluefog_tpu`` took, as the package's
``__init__`` stamped it (gauge ``import.total_sec``; the import groups on a
printed line: a module someone imported before costs its group nothing).
Inside the harness's ``import_s``. Also prints the set-up block
(``benchmark/setup_parts.py``). ``None`` on a program without the stamps."""

from benchmark import setup_parts


def read(run):
    seconds = setup_parts.import_seconds()
    if seconds is None:
        return None
    groups = sorted(((s, g) for g, s in seconds.items() if g != "total"), reverse=True)
    print(f"import bluefog_tpu {seconds['total']:.3f} s: "
          + ", ".join(f"{g} {s:.3f}" for s, g in groups))
    setup_parts.account(run)
    return seconds["total"]
