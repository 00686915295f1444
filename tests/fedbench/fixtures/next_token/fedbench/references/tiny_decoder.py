"""The fixture's reference: the program's own decoder in float32 (what
the test holds is the seam, not this model) under the harness's masked
mean cross-entropy of ``y [n, l]`` next-token labels."""

import jax.numpy as jnp

from fedbench.reference import masked_mean_cross_entropy


def make_loss(config):
    from baton_tpu.models.llama import LlamaConfig, llama_lm_model

    model = llama_lm_model(LlamaConfig(
        vocab_size=config["vocab_size"],
        max_len=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], rope_theta=config["rope_theta"]),
        compute_dtype=jnp.float32)

    def loss(params, x, y, mask):
        return masked_mean_cross_entropy(
            model.apply(params, {"x": x}, None), y, mask)

    return loss
