"""Model FLOP utilization of the whole train step, in %: the forward and
backward FLOPs of one step's tokens (recompute not counted) over the
median interval between consecutive train-step programs (pairs around an
SVD refresh left out), over the bf16 peak."""
from bench import flops, readers


def read(run):
    _, plain = readers.step_pairs(run)
    step_s = readers.median_or_none(b.start - a.start for a, b in plain)
    if not step_s:
        return None
    per_tok = flops.train_flops_per_token(run.model, run.info["seq_len"])
    work = per_tok * run.info["tokens_per_step"]
    return 100.0 * work / step_s / run.peaks["bf16_flops"]
