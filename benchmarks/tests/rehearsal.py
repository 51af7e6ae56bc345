"""Tiny sizes for driving the harness on the CPU.  Only tests import
this; the measuring command never sees it."""

TINY_GPT2 = {"vocab_size": 512, "n_layer": 2, "n_embd": 128, "n_head": 2,
             "n_inner": 512, "n_positions": 128, "n_ctx": 128}
TRAIN = {"config": TINY_GPT2,
         "mix": {"seq_len": 128, "batch_per_chip": 4,
                 "reference_rows_per_chip": 2}}
#: initializer_range 0.2 gives the tiny model logits as wide as the full
#: model's, so that a lower precision moves them as far
SERVE = {"config": {**TINY_GPT2, "initializer_range": 0.2},
         "program": {"max_len": 128, "prompt_buckets": [16, 32, 64]},
         "mix": {"prompt_len": {"dist": "lognormal", "median": 20,
                                "sigma": 0.9, "min": 4, "max": 64},
                 "answer_len": {"dist": "lognormal", "median": 10,
                                "sigma": 0.7, "min": 2, "max": 32},
                 "max_total_tokens": 90}}
CELLS = {"gpt2-medium.train-1k": TRAIN, "gpt2-large.chat-closed8": SERVE}
#: needs four devices: XLA_FLAGS=--xla_force_host_platform_device_count=4
FOUR_CHIP_CELLS = {"gpt2-medium.train-1k-dp4": TRAIN}
