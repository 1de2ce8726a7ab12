"""How the benchmark meets the program: its model configuration built from
a configuration file, and a check that the weights the benchmark makes
have the program's layout."""
from __future__ import annotations

import dataclasses


def model_config(config: dict, run: dict):
    """The program's ModelConfig, with every size taken from the file and
    the dtypes and remat policy of the path that runs it."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(config["model"]), num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"],
        sliding_window=int(config.get("sliding_window") or 0), **run)


def check_layout(params, model) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of
    ``model.init``."""
    import jax
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    spec = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    if spec(params) != spec(want):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")
