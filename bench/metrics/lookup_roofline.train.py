"""`lookup_roofline.train`: the lattice lookup's necessary HBM bytes
(`flops.lookup_bytes`, forward and backward) over the chip's peak
bandwidth, as a share of the device time of the operations that implement
the value table's gather and its backward.

Those operations are found by shape, whatever implements them: on a TPU a
device event is named by its HLO instruction, and the gather and the
scatter-add both carry the table's shape `f32[rows,m]` and the flat row
ids `s32[tokens*heads*top_k]`.  The token embedding's gather has neither.
No match: no reading."""


def matched_ops(ops: dict, conf: dict, tokens: int) -> dict:
    lr = conf["lram"]
    table = f"f32[{2 ** lr['log2_locations']},{lr['m']}]"
    ids = f"s32[{tokens * lr['heads'] * lr['top_k']}]"
    return {n: v for n, v in ops.items() if table in n and ids in n}


def read(ctx):
    trace, inputs, peak = ctx["trace"], ctx["inputs"], ctx["peak"]
    if not trace or not peak or not inputs.get("tokens_per_step"):
        return None
    conf = ctx["cell"]["config"]
    tokens = inputs["tokens_per_step"]
    ops = matched_ops(trace.get("ops", {}), conf, tokens)
    seconds = sum(v["s"] for v in ops.values())
    if not ops or seconds <= 0:
        return None
    steps = max(v["count"] for v in ops.values())
    need = ctx["flops"].lookup_bytes(conf, tokens, backward=True) * steps
    return 100.0 * need / peak["hbm_bytes_per_s"] / seconds
