"""Model FLOP/s utilization of serving: the FLOPs of every finished
request's real prompt and generated tokens, over the window, over chips
times the bf16 peak. Padding and idle decode slots do not count."""


def read(rec):
    if rec.get("kind") != "serve":
        return None
    return 100.0 * rec["flops"] / rec["window_s"] / (
        rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
