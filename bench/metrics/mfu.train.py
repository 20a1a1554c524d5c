"""`mfu.train`: model FLOPs per token (forward and backward, from shapes)
times the window's tokens per second, as a share of the chip's bf16 peak
(float32 products run as one bf16 pass at the default precision)."""


def read(ctx):
    inputs, peak = ctx["inputs"], ctx["peak"]
    if not peak or not inputs.get("steps"):
        return None
    per_token = ctx["flops"].train_flops_per_token(ctx["cell"]["config"],
                                                   inputs["seq_len"])
    rate = inputs["steps"] * inputs["tokens_per_step"] / inputs["elapsed_s"]
    return 100.0 * per_token * rate / peak["bf16_flops"]
