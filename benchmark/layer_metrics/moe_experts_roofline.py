"""Layer ``parallel.expert``: the held experts' grouped products as a share of
their roofline, in percent -- the least time the chip could take for them over
the time under ``bf.moe.experts`` (``moe_experts_ms_per_step``).

What the algorithm needs a layer, at the rows uniform routing sends here
(tokens x experts per token x held / scored): three products of [rows, d] by
[d, f] forward and twice that backward, 18 rows d f FLOPs; every held expert's
three matrices read forward and again backward and their three gradients
written, 9 held d f elements; the rows read and written around each product,
3 (2 d + 3 f) rows elements; all of 2 bytes. The roof is the larger of FLOPs
over the bf16 peak and bytes over the HBM peak: with a few hundred rows an
expert the weights' bytes bind.
"""

from benchmark import scopes


def needs(cfg: dict, batch: dict):
    """(FLOPs, bytes) of one step's grouped products."""
    d, f, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    layers = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
              + cfg["num_nextn_predict_layers"])
    rows = (batch["sequences"] * batch["seq_len"] * cfg["num_experts_per_tok"] * held
            / cfg["published"]["n_routed_experts"])
    return (18.0 * layers * rows * d * f,
            2.0 * layers * (9 * held * d * f + 3 * rows * (2 * d + 3 * f)))


def roof_seconds(cfg: dict, batch: dict, peaks: dict):
    """(least seconds a step, which roof binds)."""
    flops, bytes_ = needs(cfg, batch)
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "mxu" if by_flops >= by_bytes else "hbm"


def read(run):
    taken = scopes.ms(run, "bf.moe.experts")
    if not taken:
        return None
    roof, binds = roof_seconds(run.cell.config, run.cell.traffic["batch"], run.peaks)
    print(f"moe experts roofline: {roof * 1e3:.3f} ms a step at the {binds} roof, "
          f"{taken:.3f} ms taken")
    return 100.0 * roof * 1e3 / taken
