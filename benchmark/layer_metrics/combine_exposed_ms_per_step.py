"""Layer ``ops.plan`` (``spmd_combine``): device time a step spends on a
collective that nothing hides, on the chip where it is largest -- the ops that
are a collective or wait for one (``collective-permute-done``, a synchronous
``all-reduce``, ...), and any idle stretch of the window during which a
collective was in flight. The weighted accumulate after the permute is plain
compute and is not in here. Exactly 0 on one chip."""


def _overlap(gaps, spans):
    return sum(max(0.0, min(b, s.end) - max(a, s.start)) for a, b in gaps for s in spans)


def read(run):
    if not run.chips:
        return None
    worst = 0.0
    for chip in run.chips:
        waits = sum(op.seconds for op in chip.ops
                    if op.collective and not op.opcode.endswith("-start"))
        in_flight = [s for s in chip.in_flight if s.collective]
        worst = max(worst, waits + _overlap(chip.gaps(), in_flight))
    return worst / run.traced_steps * 1e3
