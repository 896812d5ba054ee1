"""Model FLOPs per token of a training step (three forward passes, by the
model's mathematics: the configuration's model module) times the window's
tokens per second, over the chip's bf16 peak."""


def read(rec):
    if rec["kind"] != "train":
        return None
    rate = rec["tokens"] / (rec["w1"] - rec["w0"])
    flops = rec["model"].train_flops_per_token(rec["m"], rec["seq"])
    return 100.0 * flops * rate / rec["peaks"]["bf16_flops_per_s"]
