"""Overrides that shrink each cell to a size a CPU test run holds."""
MODEL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 256}

OVERRIDES = {
    # at this size most tiles hold no detection: every tile that finished
    # in the window is checked
    "em.detect": {"config": {"volume_shape": [1024, 512, 64]},
                  "traffic": {"tile": [128, 128, 16], "workers": 3,
                              "check_tiles": 64}},
    "smollm.train": {"config": MODEL,
                     "traffic": {"batch": 2, "seq_len": 64, "docs": 64,
                                 "doc_cuboid": 8}},
}
