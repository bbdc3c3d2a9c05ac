"""Layer ``parallel.expert``: the held ReGLU experts' grouped products as a
share of their roofline, in percent -- the least time the chip could take for
them over the time under ``bf.moe.experts`` (``moe_experts_ms_per_step``).
What ``moe_experts_roofline`` counts, at this family's keys.

What the algorithm needs a layer, at the rows uniform routing sends here
(tokens x experts per token x held / scored): three products of [rows, d] by
[d, f] forward and twice that backward, 18 rows d f FLOPs; every held expert's
three matrices read forward and again backward and their three gradients
written, 9 held d f elements; the rows read and written around each product,
3 (2 d + 3 f) rows elements; all of 2 bytes. The roof is the larger of FLOPs
over the bf16 peak and bytes over the HBM peak: at 1,536 rows an expert the
matrix unit binds (a few hundred rows an expert are bound by the weights' bytes).
"""

from benchmark import scopes


def needs(family, cfg: dict, batch: dict):
    """(FLOPs, bytes) of one step's grouped products."""
    d, f, held = cfg["hidden_size"], cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"]
    layers, rows = cfg["num_hidden_layers"], family.expected_rows(cfg, batch)
    return (18.0 * layers * rows * d * f,
            2.0 * layers * (9 * held * d * f + 3 * rows * (2 * d + 3 * f)))


def roof_seconds(family, cfg: dict, batch: dict, peaks: dict):
    """(least seconds a step, which roof binds)."""
    flops, bytes_ = needs(family, cfg, batch)
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "mxu" if by_flops >= by_bytes else "hbm"


def read(run):
    taken = scopes.ms(run, "bf.moe.experts")
    if not taken or "moe_ffn_hidden_size" not in run.cell.config:
        return None
    roof, binds = roof_seconds(run.cell.family, run.cell.config, run.cell.traffic["batch"],
                               run.peaks)
    print(f"reglu experts roofline: {roof * 1e3:.3f} ms a step at the {binds} roof, "
          f"{taken:.3f} ms taken")
    return 100.0 * roof * 1e3 / taken
