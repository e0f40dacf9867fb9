"""Model FLOPs of the real tokens of each traced mixed step (from the
step's ``lens`` and ``chunk_lens``, not the padded rows), over the step's
device time times the chip's bf16 peak, %."""


def read(data):
    tr = data.get("trace")
    if data["kind"] != "serve" or tr is None or not data.get("model_flops"):
        return None
    n, sec = tr.module_time("jit__step_fn")
    if not n:
        return None
    return 100.0 * data["model_flops"] / (sec
                                          * data["peaks"]["bf16_flops_per_s"])
