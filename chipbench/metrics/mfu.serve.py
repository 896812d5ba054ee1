"""Model FLOPs of the work the window completed (each decode token at its
context, each prompt whose first token came in the window with its whole
prefill, counted by the configuration's model module), over the window,
over the chip's bf16 peak."""
from chipbench import serve


def read(rec):
    if rec["kind"] != "serve":
        return None
    flops = serve.flops_in_window(rec)
    return (100.0 * flops / (rec["w1"] - rec["w0"])
            / rec["peaks"]["bf16_flops_per_s"])
