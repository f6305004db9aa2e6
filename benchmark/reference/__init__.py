"""Plain references: the same semantics in straightforward numpy and
jax.numpy at float32 `highest`, importing nothing of the program."""
