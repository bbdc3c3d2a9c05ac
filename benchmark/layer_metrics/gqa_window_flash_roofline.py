"""Layer ``parallel.flash``: the two flash kernels' share of their roofline
under grouped-query heads and a sliding window, in percent -- the least time
the chip could take for the attention of one step over the time of the Mosaic
ops under ``bf.flash.*``.

What the algorithm needs, from the shapes: six products (QK^T and PV forward,
dV, dP, dQ and dK backward) of 2 D FLOPs a live (row, column) pair and query
head -- 12 Hq D pairs a layer, the pairs being S (S + 1) / 2 where a layer sees
the whole past and the sum over t of min(t + 1, W) under a window of W (the
family's ``attention_pairs``), so the window's skip is credited as work not
needed, and neither the scores the backward builds again nor the masked half
of an edge tile count -- and one pass over q, o, dO and dq at the Hq query
heads and over k, v, dk and dv at the Hkv k/v heads, 2 bytes an element: a
K or V repeated for its group would not be credited, nor are the per-q-head
dk/dv partials the kernel writes before XLA sums a group's. The roof is the
larger of FLOPs over the bf16 peak and bytes over the HBM peak.
"""

from benchmark import phases


def needs(family, cfg: dict, batch: dict):
    """(FLOPs, bytes) of one step's attention."""
    width = cfg["head_dim"]
    elements = (batch["sequences"] * batch["seq_len"] * width * cfg["num_hidden_layers"]
                * 4 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"]))
    return family.attention_flops(cfg, batch), 2.0 * elements


def roof_seconds(family, cfg: dict, batch: dict, peaks: dict):
    """(least seconds a step, which roof binds)."""
    flops, bytes_ = needs(family, cfg, batch)
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "mxu" if by_flops >= by_bytes else "hbm"


def read(run):
    taken = [phases.kernel_ms(run, kernel) for kernel in phases.KERNELS]
    if not any(taken) or not hasattr(run.cell.family, "attention_pairs"):
        return None
    taken = sum(ms or 0.0 for ms in taken)
    roof, binds = roof_seconds(run.cell.family, run.cell.config, run.cell.traffic["batch"],
                               run.peaks)
    print(f"gqa window flash roofline: {roof * 1e3:.3f} ms a step at the {binds} roof, "
          f"{taken:.3f} ms taken")
    return 100.0 * roof * 1e3 / taken
