"""The plain references against the program's forward pass, at a tiny size
on the CPU, on the weights and adapters the benchmark makes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program
from bench.reference.common import CONTROLS
from bench.spec import BENCH, load_module

LORA = {"rank": 4, "alpha": 4, "targets": ["wq", "wv"], "adapters": 1}
CONFIGS = {
    "dense": {"name": "tiny-dense", "family": "dense",
              "program_arch": "mistral-nemo-12b", "num_hidden_layers": 2,
              "hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "intermediate_size": 128, "vocab_size": 257,
              "rms_norm_eps": 1e-5, "rope_theta": 1e6,
              "tie_word_embeddings": False, "lora": LORA},
    "rwkv6": {"name": "tiny-rwkv6", "family": "rwkv6",
              "program_arch": "rwkv6-7b", "num_hidden_layers": 2,
              "hidden_size": 64, "intermediate_size": 128,
              "vocab_size": 257, "layer_norm_epsilon": 1e-5,
              "head_size": 16, "time_mix_extra_dim": 8,
              "time_decay_extra_dim": 16, "lora": LORA},
}


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_reference_matches_the_program(family):
    from repro.models import transformer as tfm
    c = CONFIGS[family]
    ref = load_module(BENCH / "reference" / f"{family}.py")
    cfg = program.model_config(c, ref)
    key = jax.random.PRNGKey(3)
    w = ref.make_weights(c, key)
    ad = ref.make_adapter(c, jax.random.fold_in(key, 1))
    program.check_layout(cfg, w, ad)
    w32 = jax.tree.map(lambda x: x.astype(jnp.float32), w)
    tokens = jax.random.randint(jax.random.fold_in(key, 2), (40,), 0, 257)
    got, _, _ = tfm.forward(cfg, w32, {"tokens": tokens[None]}, lora=ad,
                            mode="train")
    h = ref.hidden(c, w, ad, tokens)
    want = h @ w["embed"]["unembed"].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # each control, one precision step down, is visibly off
    for ctrl in CONTROLS:
        hc = ref.hidden(c, w, ad, tokens, ctrl)
        assert float(jnp.max(jnp.abs(hc - h))) > 1e-2, ctrl
