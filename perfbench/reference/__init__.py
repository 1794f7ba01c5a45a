"""Plain references: float32 jax.numpy at matmul precision `highest`, no
kernels, no cache, no batching tricks. They import nothing of the program."""
