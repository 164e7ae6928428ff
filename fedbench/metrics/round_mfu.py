"""The whole round's model FLOP/s over the chips' bf16 peak, in percent.

Counted per round: 3 x forward FLOPs for every trained sample of every
participating client, and 1 x forward FLOPs for every evaluated sample
of every tester x tested model (the cohort's models, and in a cohort
round the global model each tester scores for the clients outside it).
Recomputation, the global evaluation and non-participants' work are left
out. The denominator is the bf16 peak even where the model computes in
float32 (the paper's CNN), so that every cell is held to one peak.
"""
from fedbench import flops, peaks


def read(ctx):
    w, cfg = ctx.cell.work, ctx.cell.cfg
    s = ctx.summary
    if not ctx.rounds or s.window_s <= 0 or not s.devices:
        return None
    fed, train = w["federation"], w["train"]
    sample = tuple(w["sample_shape"])
    total = 0
    for p in ctx.participants:
        clients = round(p)
        tested = clients + (1 if w["engine"] == "population" else 0)
        total += flops.round_model_flops(
            cfg, sample, trained_clients=clients,
            local_steps=fed["local_steps"], batch=train["batch_size"],
            testers=fed["num_testers"], tested_models=tested,
            eval_rows=w["eval_batch"])
    peak = peaks.peaks(ctx.kind)["bf16_flops_per_s"]
    return 100.0 * total / s.window_s / (ctx.chips * peak)
