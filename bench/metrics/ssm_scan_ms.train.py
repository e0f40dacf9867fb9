"""Device milliseconds of self time under the program's ``ssm_scan`` scope
(inside a Mamba block: the causal convolution and the selective scan with
its skip and gate; forward, recompute and backward) per complete execution
of the training step, a fusion counted under the layer of its matmuls
(``scopes.buckets``). The block's projections count under ``recurrent``.
None where the scope never occurs, or the program names no such scope."""
from bench import scopes


def read(data):
    obs = scopes.program_obs()
    scope = getattr(obs, "SSM_SCAN", None)
    return scopes.train_ms(data, scope) if scope else None
