"""Tiny sizes the CPU tests run the cells at."""

TINY_OLMO = {"hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256}
