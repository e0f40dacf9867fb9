"""Device milliseconds of self time under the program's ``attn`` scope (first
norm, attention with its LoRA, residual add; forward, recompute and
backward) per complete execution of the training step, a fusion counted
under the layer of its matmuls (``scopes.buckets``); None where the scope
never occurs."""
from bench import scopes


def read(data):
    obs = scopes.program_obs()
    return scopes.train_ms(data, obs.ATTN) if obs else None
