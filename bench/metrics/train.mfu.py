"""train.mfu: the training window's model FLOPs over the chip's peak.

Model FLOPs per token come from the shapes of the run's param tree (the
ranks the decomposer built) for each freezing phase: forward, the input
gradient and the trainable factor group's weight gradient, nothing
recomputed (``harness.flops.train_flops_per_token``).  Tokens and seconds
are the window's, both halves.  Moves ``train_tokens_per_s``.
"""

from harness import core, flops


def read(ctx):
    r = ctx.readings
    if "steps" not in r:
        return None
    work = sum(r["steps"][ph] * r["tokens_per_step"]
               * flops.train_flops_per_token(r["abstract"], ctx.config,
                                             r["seq_len"], ph)
               for ph in (0, 1))
    seconds = r["half_s"][0] + r["half_s"][1]
    peak = core.peaks(core.device_info()["kind"])["bf16_flops_per_s"]
    return 100.0 * work / seconds / peak
