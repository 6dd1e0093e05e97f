"""Model FLOP/s utilization of training: the benchmark's own FLOPs per
trained token times tokens per second, over chips times the bf16 peak."""


def read(rec):
    if rec.get("kind") != "train":
        return None
    return 100.0 * rec["flops_per_token"] * rec["tokens_per_s"] / (
        rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
