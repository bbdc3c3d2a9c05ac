"""Layer ``parallel.flash``: the flash kernels' share of their roofline at a
q.k width that differs from the v width, in percent -- the least time the chip
could take for the attention of one step over the time the three kernels took
(``mla_flash_ms_per_step``).

What the algorithm needs, from the shapes (causal, so half the score matrix):
forward QK^T (d_qk) and PV (d_v), backward dV and dP (d_v), dQ and dK (d_qk) --
3 B H S^2 (d_qk + d_v) FLOPs a layer (the scores the two backward kernels build
again are recomputation and do not count) -- and one pass over q, k, v, o and
their four gradients, 2 B S H (2 d_qk + 2 d_v) elements of 2 bytes. The roof is
the larger of FLOPs over the bf16 peak and bytes over the HBM peak.
"""

from benchmark import scopes


def needs(cfg: dict, batch: dict):
    """(FLOPs, bytes) of one step's attention."""
    heads = cfg["num_attention_heads"]
    d_qk, d_v = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    b, s = batch["sequences"], batch["seq_len"]
    return (3.0 * layers * b * heads * s * s * (d_qk + d_v),
            2.0 * layers * b * s * heads * (2 * d_qk + 2 * d_v) * 2)


def roof_seconds(cfg: dict, batch: dict, peaks: dict):
    """(least seconds a step, which roof binds)."""
    flops, bytes_ = needs(cfg, batch)
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "mxu" if by_flops >= by_bytes else "hbm"


def read(run):
    taken = scopes.ms(run, *scopes.FLASH)
    if not taken:
        return None
    roof, binds = roof_seconds(run.cell.config, run.cell.traffic["batch"], run.peaks)
    print(f"mla flash roofline: {roof * 1e3:.3f} ms a step at the {binds} roof, "
          f"{taken:.3f} ms taken")
    return 100.0 * roof * 1e3 / taken
