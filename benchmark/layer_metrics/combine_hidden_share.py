"""Layer ``ops.plan``: the share, in percent, of the time collectives are in
flight that the chip spends on something else -- 100 * (1 - exposed / in
flight), on the chip where it is smallest. ``exposed`` is what
``combine_exposed_ms_per_step`` sums (the ops that are a collective or wait for
one, and idle gaps with a collective in flight); ``in flight`` is the union of
the ``Async XLA Ops`` spans of collectives, start to done. 0: every transfer is
waited for from its first byte to its last; 100: none is ever waited for.
Nothing to read where no collective is in flight (one chip, a parent whose
collectives are synchronous)."""


def _overlap(gaps, spans):
    return sum(max(0.0, min(b, s.end) - max(a, s.start)) for a, b in gaps for s in spans)


def _union(spans):
    total, reach = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s.start):
        total += max(0.0, s.end - max(s.start, reach))
        reach = max(reach, s.end)
    return total


def read(run):
    shares = []
    for chip in run.chips:
        in_flight = [s for s in chip.in_flight if s.collective]
        if not in_flight:
            continue
        waits = sum(op.seconds for op in chip.ops
                    if op.collective and not op.opcode.endswith("-start"))
        exposed = waits + _overlap(chip.gaps(), in_flight)
        shares.append(1.0 - exposed / _union(in_flight))
    return 100.0 * min(shares) if shares else None
