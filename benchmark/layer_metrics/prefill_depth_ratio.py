"""Layer-tokens the window's prefills computed over what every layer over
every token would have been (the engine recorder's ``prefill_layer_tokens``
and ``prefill_layer_tokens_whole``, counted on the host from the prompts'
lengths): a decoder whose later layers read one layer's keys and values runs
those layers for the prompt's last token alone, 17 of 32 layers over the
prompt here, ~0.53. An engine whose prefill runs every layer over every token
records neither counter."""


def read(run):
    engine = run.get("engine", {})
    whole = engine.get("prefill_layer_tokens_whole")
    return engine["prefill_layer_tokens"] / whole if whole else None
