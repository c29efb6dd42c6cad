"""The one translation from a configuration file (the published
``config.json`` keys) to the program's ``GPTConfig``."""


def gpt_config(config: dict):
    from hetu_tpu.models import GPTConfig
    if config["activation_function"] != "gelu":
        raise ValueError("only the GPT-2 block (gelu) is described here")
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        ffn_hidden_size=config["n_inner"], max_seq_len=config["n_positions"],
        activation="gelu", norm="layernorm", position="learned",
        tie_embeddings=bool(config["tie_word_embeddings"]),
        init_std=config["initializer_range"], sp=False,
        dtype=config["dtype"])
