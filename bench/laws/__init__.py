"""Token laws: one generator per law, each ``generate(mix, vocab_size,
instances, seed) -> int32 array (instances, seq_len + 1)``."""
