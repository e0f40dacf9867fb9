"""Device milliseconds of self time under the program's ``head`` and
``loss`` scopes together (final norm, unembedding and cross entropy over the
logits; forward and backward) per complete execution of the training step.
XLA fuses the loss's reductions over the vocabulary into the head's
matmuls, where they count under ``head`` (``scopes.buckets``), so the two
are one reading. None where neither scope occurs."""
from bench import scopes


def read(data):
    obs = scopes.program_obs()
    return scopes.train_ms(data, obs.HEAD, obs.LOSS) if obs else None
