"""Model FLOPs of the traced training steps (forward and activation
gradients of a frozen base, LoRA gradients, causal attention; recomputation
not counted) over the traced segment's seconds times the bf16 peak, %."""


def read(data):
    tr = data.get("trace")
    if data["kind"] != "train" or tr is None or not data.get("model_flops"):
        return None
    return 100.0 * data["model_flops"] / (tr.window_s
                                          * data["peaks"]["bf16_flops_per_s"])
