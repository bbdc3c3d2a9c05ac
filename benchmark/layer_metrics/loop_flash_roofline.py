"""Layer ``parallel.flash``: the two flash kernels' share of their roofline in
a looped, recomputed model, in percent -- the least time the chip could take
for the attention one step needs over the time of the Mosaic ops under
``bf.flash.*``.

What the algorithm needs, from the shapes, a layer application: six products
(QK^T and PV forward, dV, dP, dQ and dK backward) of 2 D FLOPs a live (row,
column) pair and head, 12 H D S (S + 1) / 2, and one pass over q, k, v, o, dO,
dq, dk and dv at 2 bytes an element; times passes x layers applications (the
family's ``applications``). The forward kernel a recomputed application runs
again is time taken and no work needed, as are the scores the backward
rebuilds: the share falls by what recomputation costs the kernels. The roof is
the larger of FLOPs over the bf16 peak and bytes over the HBM peak.
"""

from benchmark import phases


def needs(family, cfg: dict, batch: dict):
    """(FLOPs, bytes) of one step's attention."""
    elements = (8 * batch["sequences"] * batch["seq_len"] * cfg["num_attention_heads"]
                * cfg["head_dim"] * family.applications(cfg))
    return family.attention_flops(cfg, batch), 2.0 * elements


def roof_seconds(family, cfg: dict, batch: dict, peaks: dict):
    """(least seconds a step, which roof binds)."""
    flops, bytes_ = needs(family, cfg, batch)
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "mxu" if by_flops >= by_bytes else "hbm"


def read(run):
    taken = [phases.kernel_ms(run, kernel) for kernel in phases.KERNELS]
    if not any(taken) or not hasattr(run.cell.family, "applications"):
        return None
    taken = sum(ms or 0.0 for ms in taken)
    roof, binds = roof_seconds(run.cell.family, run.cell.config, run.cell.traffic["batch"],
                               run.peaks)
    print(f"loop flash roofline: {roof * 1e3:.3f} ms a step at the {binds} roof, "
          f"{taken:.3f} ms taken")
    return 100.0 * roof * 1e3 / taken
