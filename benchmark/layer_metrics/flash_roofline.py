"""Layer ``parallel.flash``: the flash kernels' share of their roofline, in
percent -- the least time the chip could take for the attention of one step
over the time the three kernels took (``flash_ms_per_step``).

What the algorithm needs, from the shapes (causal, so half the score matrix):
forward QK^T and PV, backward dV, dP, dQ, dK -- 12 B H S^2 D / 2 FLOPs a layer
(the scores the two backward kernels build again are recomputation and do not
count) -- and one pass over q, k, v, o and their four gradients, 8 B S H D
elements of 2 bytes. The roof is the larger of FLOPs over the bf16 peak and
bytes over the HBM peak; at these sequence lengths the matrix unit binds.
"""


def roof_seconds(cfg: dict, batch: dict, peaks: dict):
    """(least seconds a step, which roof binds)."""
    layers, width = cfg["num_hidden_layers"], cfg["hidden_size"]  # width = heads x head size
    b, s = batch["sequences"], batch["seq_len"]
    flops = 12.0 * layers * b * s * s * width * 0.5
    bytes_ = 8.0 * layers * b * s * width * 2
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "mxu" if by_flops >= by_bytes else "hbm"


def read(run):
    if not run.chips:
        return None
    chip = run.trace.busiest
    seconds = sum(op.seconds for op in chip.ops if op.is_mosaic) / run.traced_steps
    if seconds == 0.0:
        return None
    roof, binds = roof_seconds(run.cell.config, run.cell.traffic["batch"], run.peaks)
    print(f"flash roofline: {roof * 1e3:.3f} ms a step at the {binds} roof, "
          f"{seconds * 1e3:.3f} ms taken")
    return 100.0 * roof / seconds
